"""The served path as a user drives it, and the load that drives it.

``build_stack`` puts the program together from a configuration:
``FrontEnd(ServeEngine(LocalBackend(index)))``.  ``warm`` compiles every
shape the cell's traffic will dispatch, before the window.  ``closed_loop``
and ``open_loop`` are the two load generators; every request goes through
``FrontEnd.submit``, the program's entry point for traffic.
"""
from __future__ import annotations

import asyncio
import itertools
import math
import time
from dataclasses import dataclass, field

import numpy as np

from workload import rng_for, ORDER, to_program_filter

SETTLE_S = 60.0        # how long past the window an answer may still come


def build_stack(cfg: dict, index):
    """(FrontEnd, ServeEngine) over a LocalBackend of ``index``."""
    from repro.core import (BatchSpec, FrontEndSpec, LocalBackend, ObsSpec,
                            SearchOptions)
    from repro.serving import FrontEnd, ServeEngine
    s = cfg["search"]
    opts = SearchOptions(k=s["k"], ef=s["ef"], use_pallas=s["use_pallas"],
                         use_pq=s["use_pq"], rerank=s.get("rerank"),
                         graph_quant=s.get("graph_quant"),
                         batch=BatchSpec(**cfg["batch"]))
    eng = ServeEngine(LocalBackend(index), opts,
                      max_batch=cfg["engine"]["max_batch"],
                      obs=ObsSpec(**cfg["obs"]))
    return FrontEnd(eng, FrontEndSpec(**cfg["frontend"])), eng


@dataclass
class Requests:
    """What happened to every request of one phase."""
    k: int
    items: list = field(default_factory=list)
    due: list = field(default_factory=list)
    sent: list = field(default_factory=list)
    done: list = field(default_factory=list)     # nan: no answer
    status: list = field(default_factory=list)   # "ok" | "shed" | "error"
    ids: list = field(default_factory=list)
    dists: list = field(default_factory=list)
    routes: list = field(default_factory=list)
    errors: list = field(default_factory=list)

    def add(self, item: int, due: float, sent: float) -> int:
        self.items.append(item)
        self.due.append(due)
        self.sent.append(sent)
        self.done.append(math.nan)
        self.status.append(None)
        self.ids.append(None)
        self.dists.append(None)
        self.routes.append(None)
        return len(self.items) - 1

    def settle(self, i: int, resp=None, status: str = "ok") -> None:
        self.done[i] = time.perf_counter()
        self.status[i] = status
        if resp is not None:
            self.ids[i] = np.asarray(resp.ids)[:self.k]
            self.dists[i] = np.asarray(resp.dists)[:self.k]
            self.routes[i] = resp.route


class Submitter:
    """One request through ``FrontEnd.submit``, recorded in ``log``."""

    def __init__(self, fe, pool, log: Requests):
        from repro.serving import Overloaded
        self.fe, self.pool, self.log = fe, pool, log
        self._shed = Overloaded
        self._flts = [to_program_filter(f) for f in pool.filters]

    async def __call__(self, item: int, due: float) -> None:
        i = self.log.add(item, due, time.perf_counter())
        try:
            resp = await self.fe.submit(
                self.pool.queries[item],
                self._flts[self.pool.filter_of[item]])
        except self._shed:
            self.log.settle(i, status="shed")
        except Exception as e:      # the program failed this request
            self.log.settle(i, status="error")
            self.log.errors.append(repr(e))
        else:
            self.log.settle(i, resp)


def request_stream(pool_size: int, seed: int):
    """Pool items in seeded permutations, one after another, for ever."""
    rng = rng_for(seed, ORDER)
    while True:
        yield from rng.permutation(pool_size).tolist()


async def _settle(tasks, deadline: float) -> None:
    """Wait for every task until ``deadline`` (perf_counter); cancel the
    rest, which then count as unanswered."""
    if not tasks:
        return
    done, pending = await asyncio.wait(
        tasks, timeout=max(deadline - time.perf_counter(), 0.0))
    for t in pending:
        t.cancel()


async def closed_loop(sub: Submitter, clients: int, seconds: float,
                      seed: int, on_start=None) -> tuple[float, float]:
    """``clients`` callers, each sending its next request as soon as the
    last one returns, until ``seconds`` have passed.  Returns the window
    (start, end) on ``time.perf_counter``."""
    stream = request_stream(sub.pool.size, seed)
    t0 = time.perf_counter()
    if on_start is not None:
        on_start()
    t_end = t0 + seconds

    async def client():
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            await sub(next(stream), now)

    await _settle([asyncio.ensure_future(client()) for _ in range(clients)],
                  t_end + SETTLE_S)
    return t0, t_end


async def open_loop(sub: Submitter, times: np.ndarray, seconds: float,
                    seed: int, on_start=None) -> tuple[float, float, np.ndarray]:
    """Requests due at ``times`` (seconds into the window), sent whether or
    not earlier ones returned.  Returns the window and how late each
    request was sent (seconds)."""
    stream = request_stream(sub.pool.size, seed)
    tasks = []
    late = np.zeros(len(times))
    t0 = time.perf_counter()
    if on_start is not None:
        on_start()
    i = 0
    while i < len(times):
        now = time.perf_counter() - t0
        while i < len(times) and times[i] <= now:
            late[i] = now - times[i]
            tasks.append(asyncio.ensure_future(sub(next(stream),
                                                   t0 + times[i])))
            i += 1
        if i < len(times):
            await asyncio.sleep(max(times[i] - (time.perf_counter() - t0), 0.0))
    t_end = t0 + seconds
    await asyncio.sleep(max(t_end - time.perf_counter(), 0.0))
    await _settle(tasks, t_end + SETTLE_S)
    return t0, t_end, late


# ---------------------------------------------------------------------------
# Warm-up
# ---------------------------------------------------------------------------
async def _one_batch(sub: Submitter, items) -> None:
    await asyncio.gather(*(sub(int(it), time.perf_counter()) for it in items))


async def warm(sub: Submitter, eng, traffic: dict, max_batch: int) -> dict:
    """Compile every shape the window can dispatch.

    1. the engine's bucket executables (``ServeEngine.warmup``);
    2. the whole pool once, in full batches: this also tells which route
       the program's selector gives each pool item;
    3. every batch size the loop can produce (a closed loop with at least
       ``max_batch`` clients dispatches full batches; an open loop any
       size), and, where both routes serve the pool, every split of a batch
       between them that the pool's route shares make likely (up to seven
       standard deviations above the mean): the router slices and pads
       each route's sub-batch at its exact size.
    """
    t0 = time.perf_counter()
    eng.warmup()
    t1 = time.perf_counter()
    log = sub.log
    pool = sub.pool
    for s in range(0, pool.size, max_batch):
        await _one_batch(sub, range(s, min(s + max_batch, pool.size)))
    route = {}
    for it, r in zip(log.items, log.routes):
        route[it] = r
    graph = np.asarray([i for i, r in route.items() if r == "graph"])
    brute = np.asarray([i for i, r in route.items() if r == "brute"])
    if traffic["loop"] == "closed":
        sizes = [min(traffic["clients"], max_batch)]
    else:
        sizes = list(range(1, max_batch + 1))
    batches = []
    both = len(graph) and len(brute)
    minority, majority = ((graph, brute) if len(graph) <= len(brute)
                          else (brute, graph))
    share = len(minority) / max(len(graph) + len(brute), 1)
    cyc_min = itertools.cycle(minority.tolist() if len(minority) else [0])
    cyc_maj = itertools.cycle(majority.tolist() if len(majority) else
                              list(range(pool.size)))
    for s in sizes:
        splits = [0]
        if both:
            hi = s * share + 7.0 * math.sqrt(s * share * (1 - share)) + 2
            splits = list(range(0, min(s, int(hi)) + 1))
            if s not in splits:
                splits.append(s)
        for m in splits:
            batches.append([next(cyc_min) for _ in range(m)]
                           + [next(cyc_maj) for _ in range(s - m)])
    t2 = time.perf_counter()
    for b in batches:
        await _one_batch(sub, b)
    return {"pool_graph": int(len(graph)), "pool_brute": int(len(brute)),
            "warm_batches": len(batches), "buckets_s": round(t1 - t0, 3),
            "pool_pass_s": round(t2 - t1, 3),
            "shapes_s": round(time.perf_counter() - t2, 3)}
