"""Serve FAVOR once on a TPU through the entry points a user calls.

    python chip_smoke.py              one chip: LocalBackend
    python chip_smoke.py --chips 4    four chips: ShardedBackend vs LocalBackend

One process drives the served path end to end:

  seeded paper-schema corpus (``synthetic.make_paper_dataset``, N x 128)
  -> FavorIndex.build (HNSW M=16 efc=100, PQ m=32 x 8 bits)
  -> FrontEnd(ServeEngine(LocalBackend(index))) -> warmup()
  -> a few hundred ``await FrontEnd.submit`` requests, each with one of the
     paper's six filters (``filters.paper_filters``) or a filter under 1%
     selectivity, so both the graph and the brute route serve traffic.

The requests run in four phases over the same index: f32 and PQ scoring,
each on the jnp path and on the Pallas kernels (``filtered_topk`` and
``gather_distance`` serve f32, ``pq_adc_topr`` and ``pq_adc_gather`` serve
PQ).  Every phase is checked against ``core.refimpl.bruteforce_filtered``:

  * f32 brute route: the exact filtered top-k (ties may swap ids);
  * PQ brute route (ADC scan + exact re-rank) and every graph route: mean
    recall@10 at least the phase's bound in ``SmokeConfig.recall_min``;
  * no compile after ``warmup()`` (``stats["batching"]["compile_events"]``);
  * each Pallas phase returns the ids of its jnp phase in >= 99% of places;
  * the PQ codes trained and encoded on the device are each row's nearest
    centroid, recomputed on the host.

``--chips 4`` runs only the sharded path: ShardedBackend.build over a (1, 4)
("data", "model") mesh on the same kind of corpus, the same phases and
checks, the DB asserted to sit on four devices, and LocalBackend on one
chip as the comparison.

Lines before the last report what the run saw; the last line is the JSON
object ``{"ok": true, "device": {...}}``.  A failed check raises, and the
script exits non-zero without that line.  The script runs only on a TPU:
``main(expect_platform=...)`` takes another platform only from the tier-1
test that rehearses the phases at a tiny size on the CPU.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.core import (And, BatchSpec, BuildSpec, Equality, FavorIndex,  # noqa: E402
                        HnswParams, LocalBackend, QuantSpec, Range,
                        SearchOptions, ShardedBackend, compile_filter,
                        paper_filters, refimpl)
from repro.core import filters as F  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro.serving import FrontEnd, ServeEngine  # noqa: E402

SEED = 0
K = 10
EF = 128
AGREE_MIN = 0.99        # Pallas vs jnp: share of equal ids
AGREE_CODES = 0.999     # device PQ codes vs host nearest centroid (near ties)
DIST_RTOL = 1e-4        # brute route: a tie may swap ids at equal distance
HNSW = HnswParams(M=16, efc=100, seed=SEED)
QUANT = QuantSpec(kind="pq", m=32, nbits=8)


@dataclass(frozen=True)
class SmokeConfig:
    """Scale of one smoke run.  ``recall_min`` maps a phase's scorer
    ("f32" / "pq") to the least mean graph-route recall@10 it must reach,
    and "pq_brute" to the least recall of the PQ brute route; each bound is
    the CPU rehearsal's value at the same N and seed, minus 0.02."""
    n: int
    dim: int = 128
    n_requests: int = 512
    bucket: int = 256
    recall_min: dict = field(default_factory=dict)


# N: the largest power of two (at least 2^16) whose host-side HNSW build
# stays near ten minutes (2^16 took 438 s on one core of an x86 CPU host;
# 2^17 would take about twice that).  The four-chip run builds the corpus
# twice (one graph per shard, then the one-chip comparison) at four times
# the chip cost per second, so it runs an eighth of that N.
# Bounds: CPU rehearsal at the same N and seed (jnp phases), minus 0.02.
# At 2^16 it measured recall@10 0.9693 (f32 graph), 0.6982 (PQ graph) and
# 0.9958 (PQ brute); at 2^13, min over sharded and local: 0.9948, 0.9516
# and 1.0.
ONE_CHIP = SmokeConfig(n=1 << 16, recall_min={"f32": 0.9493, "pq": 0.6782,
                                             "pq_brute": 0.9758})
FOUR_CHIPS = SmokeConfig(n=1 << 13, recall_min={"f32": 0.9748, "pq": 0.9316,
                                               "pq_brute": 0.98})


class SmokeError(AssertionError):
    """A smoke check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def say(*parts) -> None:
    print(*parts, flush=True)


# ---------------------------------------------------------------------------
# Workload and reference
# ---------------------------------------------------------------------------
def workload(schema) -> dict:
    """The paper's six filters plus one under 1% (~0.4%: routes brute)."""
    flts = dict(paper_filters(schema, np.random.default_rng(SEED)))
    flts["rare"] = And(Equality("i0", 3), Range("f0", 10.0, 14.0))
    return flts


def make_requests(cfg: SmokeConfig, flts: dict):
    rng = np.random.default_rng(SEED + 1)
    names = sorted(flts)
    qs = synthetic.make_queries(cfg.n_requests, cfg.dim, dataset_seed=SEED,
                                seed=SEED + 7)
    return [(qs[i], names[int(rng.integers(len(names)))])
            for i in range(cfg.n_requests)]


def reference(vecs, attrs, schema, flts, requests):
    """Exact filtered top-k per request (core.refimpl) + the filter masks."""
    masks = {name: F.eval_program(compile_filter(f, schema), attrs.ints,
                                  attrs.floats)
             for name, f in flts.items()}
    truth = [refimpl.bruteforce_filtered(vecs, masks[name], q, K)
             for q, name in requests]
    return masks, truth


# ---------------------------------------------------------------------------
# One phase: a fresh engine + front end over a shared backend
# ---------------------------------------------------------------------------
def options(cfg: SmokeConfig, scorer: str, pallas: bool) -> SearchOptions:
    opts = SearchOptions(k=K, ef=EF, use_pallas=pallas,
                         batch=BatchSpec(min_bucket=cfg.bucket,
                                         max_bucket=cfg.bucket))
    if scorer == "pq":
        opts = opts.with_(use_pq=True, graph_quant="pq")
    return opts


async def _submit_all(fe: FrontEnd, flts: dict, requests):
    try:
        return await asyncio.gather(*(fe.submit(q, flts[name])
                                      for q, name in requests))
    finally:
        await fe.close()


def run_phase(tag, backend, opts, cfg, flts, requests) -> dict:
    eng = ServeEngine(backend, opts, max_batch=cfg.bucket)
    t0 = time.perf_counter()
    eng.warmup()
    warm_s = time.perf_counter() - t0
    compiles = eng.stats["batching"]["compile_events"]
    t0 = time.perf_counter()
    resps = asyncio.run(_submit_all(FrontEnd(eng), flts, requests))
    serve_s = time.perf_counter() - t0
    st = eng.stats
    lat = np.asarray([r.latency_s for r in resps]) * 1e3
    say(f"[{tag}] warmup_s={warm_s:.2f} serve_s={serve_s:.2f} "
        f"requests graph={st['graph']} brute={st['brute']} "
        f"batches={st['batches']} p50_ms={np.percentile(lat, 50):.2f} "
        f"p99_ms={np.percentile(lat, 99):.2f}")
    check(st["batching"]["compile_events"] == compiles,
          f"{tag}: {st['batching']['compile_events'] - compiles} compile "
          f"events after warmup()")
    check(st["graph"] > 0 and st["brute"] > 0,
          f"{tag}: both routes must serve traffic (graph={st['graph']}, "
          f"brute={st['brute']})")
    ids = np.stack([np.asarray(r.ids)[:K] for r in resps])
    return {"ids": ids, "routes": [r.route for r in resps]}


def verify(tag, out, scorer, cfg, vecs, masks, truth, requests) -> dict:
    """Check one phase's answers against the exact reference."""
    recalls: dict[str, list] = {}
    brute_recalls = []
    for (q, name), ids, route, (t_ids, t_d) in zip(
            requests, out["ids"], out["routes"], truth):
        got = ids[ids >= 0]
        if route == "graph":
            recalls.setdefault(name, []).append(
                refimpl.recall_at_k(got, t_ids, K))
            continue
        if scorer == "pq":
            brute_recalls.append(refimpl.recall_at_k(got, t_ids, K))
            continue
        # exact route: same filtered rows at the same distances, in order
        check(len(got) == len(t_ids) and bool(masks[name][got].all()),
              f"{tag}: brute ids {got} vs exact {t_ids} ({name})")
        d_got = np.linalg.norm(vecs[got] - q[None, :], axis=1)
        check(bool(np.all(np.abs(d_got - t_d)
                          <= DIST_RTOL * np.maximum(1.0, t_d))),
              f"{tag}: brute distances {d_got} vs exact {t_d} ({name})")
    per_filter = {name: float(np.mean(r)) for name, r in sorted(recalls.items())}
    mean = float(np.mean([x for r in recalls.values() for x in r]))
    say(f"[{tag}] graph recall@{K} mean={mean:.4f} "
        + " ".join(f"{k}={v:.4f}" for k, v in per_filter.items()))
    check(mean >= cfg.recall_min[scorer],
          f"{tag}: graph recall {mean:.4f} < {cfg.recall_min[scorer]}")
    if scorer == "pq":
        b = float(np.mean(brute_recalls))
        say(f"[{tag}] brute recall@{K} (ADC scan + exact re-rank) = {b:.4f}")
        check(b >= cfg.recall_min["pq_brute"],
              f"{tag}: brute recall {b:.4f} < {cfg.recall_min['pq_brute']}")
    else:
        say(f"[{tag}] brute ids match the exact reference "
            f"({sum(r == 'brute' for r in out['routes'])} requests)")
    return {"recall": mean, "per_filter": per_filter}


def check_codes(tag, codes, centroids, vecs, rows: int = 1024) -> None:
    """The device-trained PQ codes of the first ``rows`` rows are each row's
    nearest centroid per subspace, recomputed on the host in float64."""
    m, _, dsub = centroids.shape
    x = np.zeros((rows, m * dsub))                  # zero-padded feature tail
    x[:, :vecs.shape[1]] = vecs[:rows]
    x = x.reshape(rows, m, dsub)
    c = np.asarray(centroids, np.float64)
    d2 = (np.sum(c * c, -1)[None] - 2.0 * np.einsum("nmd,mkd->nmk", x, c))
    share = float(np.mean(np.argmin(d2, -1) == np.asarray(codes)[:rows]))
    say(f"[{tag}] PQ codes equal the host nearest-centroid codes: {share:.4f}")
    check(share >= AGREE_CODES, f"{tag}: PQ code agreement {share:.4f} < "
                                f"{AGREE_CODES}")


def agree(tag, jnp_out, pallas_out) -> float:
    share = float(np.mean(jnp_out["ids"] == pallas_out["ids"]))
    say(f"[{tag}] pallas-vs-jnp id agreement = {share:.4f}")
    check(share >= AGREE_MIN, f"{tag}: agreement {share:.4f} < {AGREE_MIN}")
    return share


def run_phases(label, backend, cfg, flts, requests, ref, vecs, scorers):
    """jnp then Pallas for each scorer over one backend."""
    masks, truth = ref
    for scorer in scorers:
        outs = {}
        for pallas in (False, True):
            tag = f"{label}/{scorer}/{'pallas' if pallas else 'jnp'}"
            outs[pallas] = run_phase(tag, backend,
                                     options(cfg, scorer, pallas), cfg,
                                     flts, requests)
            verify(tag, outs[pallas], scorer, cfg, vecs, masks, truth,
                   requests)
        agree(f"{label}/{scorer}", outs[False], outs[True])


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------
def main(expect_platform: str = "tpu", chips: int = 1,
         cfg: SmokeConfig | None = None) -> dict:
    """Run the smoke; returns the device as JAX reports it.  Raises on any
    failed check, and at once when the platform is not ``expect_platform``
    or fewer than ``chips`` devices are visible."""
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"device platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    check(device["platform"] == expect_platform,
          f"expected platform {expect_platform!r}, JAX found "
          f"{device['platform']!r}")
    check(len(devs) >= chips, f"--chips {chips} needs {chips} devices, "
                              f"found {len(devs)}")
    cfg = cfg or (FOUR_CHIPS if chips == 4 else ONE_CHIP)

    vecs, attrs, schema = synthetic.make_paper_dataset(cfg.n, cfg.dim, SEED)
    flts = workload(schema)
    requests = make_requests(cfg, flts)
    ref = reference(vecs, attrs, schema, flts, requests)
    spec = BuildSpec(hnsw=HNSW, quant=QUANT)
    say(f"corpus n={cfg.n} d={cfg.dim} requests/phase={cfg.n_requests} "
        f"bucket={cfg.bucket} k={K} ef={EF}")

    if chips == 4:
        mesh = jax.make_mesh((1, 4), ("data", "model"))
        t0 = time.perf_counter()
        sharded = ShardedBackend.build(vecs, attrs, mesh, spec, seed=SEED)
        say(f"sharded build_s={time.perf_counter() - t0:.2f} (4 shards)")
        for name, arr in sharded.db.items():
            check(len(arr.sharding.device_set) == 4,
                  f"sharded DB array {name!r} sits on "
                  f"{len(arr.sharding.device_set)} device(s), not 4")
        check(not sharded.db["vectors"].sharding.is_fully_replicated,
              "sharded DB vectors are replicated, not sharded")
        say("sharded DB arrays sit on 4 devices")
        run_phases("sharded", sharded, cfg, flts, requests, ref, vecs,
                   ("f32", "pq"))

    t0 = time.perf_counter()
    fi = FavorIndex.build(vecs, attrs, spec=spec)
    say(f"local build_s={time.perf_counter() - t0:.2f} "
        f"(hnsw_s={fi.build_seconds:.2f}, PQ train + encode after it)")
    check_codes("local", fi.g["codes"], fi.codebook.centroids, vecs)
    local = LocalBackend(fi)
    if chips == 4:
        # the one-chip comparison: same corpus, same requests, same checks
        for scorer in ("f32", "pq"):
            tag = f"local/{scorer}/jnp"
            out = run_phase(tag, local, options(cfg, scorer, False), cfg,
                            flts, requests)
            verify(tag, out, scorer, cfg, vecs, *ref, requests)
    else:
        run_phases("local", local, cfg, flts, requests, ref, vecs,
                   ("f32", "pq"))

    stats = devs[0].memory_stats() or {}
    say(f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}")
    return device


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path on a four-chip host")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    say(f"compile cache: {enable_compile_cache(ROOT)}")
    dev = main("tpu", chips=args.chips)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
