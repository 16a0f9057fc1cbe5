"""The plain reference, the comparison that decides ``correct``, and the
control that the comparison has to catch.

Reference: the exact filtered k nearest neighbours (L2) of every pool item,
in float64 on the host, from the benchmark's own corpus and filter code.  It
imports nothing of the program.

Comparison, over every answer the window produced (``compare``):

* ``unanswered`` -- requests due in the window that never got an answer
  (neither a result nor a shed) within a minute of its close.  Limit 0.
* ``bad_answers`` -- answers that say the wrong thing outright: an id out
  of range or repeated, a row that fails the request's filter, distances
  not ascending, no id at all for a filter that passes at least
  ``GRAPH_SERVES`` of the rows, or -- on the brute route, which scans
  every row -- fewer ids than ``min(k, rows that pass)``.  Where the
  configuration's brute route is exact (``brute_exact``: it scans the
  float32 rows), a row farther than the reference's k-th (up to ``TIE`` of
  it: rows that tie may come in either order) is bad too.  Limit 0.  (The
  graph route is approximate: a short answer counts against its recall,
  not here, and so does an empty one for a filter under 1%, which only the
  selector's sampled estimate sends to the graph.)
* ``recall_miss`` -- 1 - the mean recall@k of the graph route's answers
  against the reference's top-k ids: what a traversal that stops early,
  ignores ``ef`` or prunes wrongly loses, however well each returned row
  agrees with its own distance.
* ``brute_recall_miss`` -- only where the brute route is compressed (it
  scans PQ or SQ codes and re-ranks the best ``rerank * k`` exactly): 1 -
  the mean recall@k of the brute route's answers.  Such a route promises
  recall, not exactness, so its far rows count here and not in
  ``bad_answers``.  The cell's limits must give it a limit.
* ``dist_err`` -- the mean gap between a returned distance and the exact
  distance of the returned row, over every answer and rank, each as a
  share of the exact k-th distance of that request.  The configuration
  scores in float32 at ``Precision.HIGHEST``; the control below scores one
  step down.  A mean and not the widest gap: float32 accumulation alone
  puts the widest gap of a sound run within a few times that of the
  control, while the mean gap of the control is tens of times larger.

Control (``control_answers``): the reference put in the program's place and
computed one precision step down: float32 at ``Precision.HIGH``, i.e. three
bfloat16 passes (hi*hi + hi*lo + lo*hi), emulated exactly here so that it
reads the same on any platform.
"""
from __future__ import annotations

import numpy as np

from workload import eval_filter

BLOCK = 256
GRAPH_SERVES = 0.01    # the selectivity from which FAVOR routes to the graph
TIE = 1e-4             # brute route: rows within this share of the k-th tie


def exact_topk(vecs: np.ndarray, ints, floats, cols: dict, pool, k: int,
               items: np.ndarray) -> dict:
    """Exact filtered top-k of the pool ``items``: ``ids`` (I, k) int64
    (-1 past the rows that pass), ``d`` (I, k) float64 (+inf there),
    ``n_match`` (I,) rows that pass each item's filter."""
    x = vecs.astype(np.float64)
    xn = np.einsum("nd,nd->n", x, x)
    masks: dict[int, np.ndarray] = {}
    n_items = len(items)
    ids = np.full((n_items, k), -1, np.int64)
    dist = np.full((n_items, k), np.inf)
    n_match = np.zeros(n_items, np.int64)
    for s in range(0, n_items, BLOCK):
        blk = items[s:s + BLOCK]
        q = pool.queries[blk].astype(np.float64)
        d2 = xn[None, :] + np.einsum("bd,bd->b", q, q)[:, None] - 2.0 * (q @ x.T)
        for r, item in enumerate(blk):
            fi = int(pool.filter_of[item])
            if fi not in masks:
                masks[fi] = eval_filter(pool.filters[fi], ints, floats, cols)
            m = masks[fi]
            n_match[s + r] = int(m.sum())
            row = np.where(m, d2[r], np.inf)
            kk = min(k, n_match[s + r])
            if kk == 0:
                continue
            part = np.argpartition(row, kk - 1)[:kk]
            part = part[np.argsort(row[part], kind="stable")]
            ids[s + r, :kk] = part
            dist[s + r, :kk] = np.sqrt(np.maximum(row[part], 0.0))
        if len(masks) > 64:             # per-request filters: keep few
            masks.clear()
    return {"ids": ids, "d": dist, "n_match": n_match}


def recall_at_k(got: np.ndarray, ref_ids: np.ndarray, k: int) -> np.ndarray:
    """(A,) recall@k of each answer row against its reference row."""
    out = np.empty(len(got))
    for a in range(len(got)):
        t = ref_ids[a][ref_ids[a] >= 0][:k]
        if len(t) == 0:
            out[a] = 1.0
            continue
        g = got[a][got[a] >= 0][:k]
        out[a] = len(np.intersect1d(g, t)) / min(k, len(t))
    return out


def compare(ans_items: np.ndarray, ans_ids: np.ndarray, ans_d: np.ndarray,
            ans_brute: np.ndarray, n_unanswered: int, vecs, ints, floats,
            cols: dict, pool, k: int, *,
            brute_exact: bool) -> tuple[dict, dict]:
    """Check every answer (pool item ``ans_items[a]`` answered with
    ``ans_ids[a]`` / ``ans_d[a]``, by the brute route where
    ``ans_brute[a]``) against the exact reference, holding the brute route
    to exactness where ``brute_exact`` and to its recall otherwise.
    Returns (numbers compared, diagnostics including the mean recall@k)."""
    n = vecs.shape[0]
    uniq, inv = np.unique(ans_items, return_inverse=True)
    ref = exact_topk(vecs, ints, floats, cols, pool, k, uniq)
    ref_ids, ref_d, n_match = ref["ids"][inv], ref["d"][inv], ref["n_match"][inv]
    ids = np.asarray(ans_ids, np.int64)[:, :k]
    d = np.asarray(ans_d, np.float64)[:, :k]
    valid = ids >= 0
    n_ret = valid.sum(1)

    oor = ((ids < -1) | (ids >= n)).any(1)
    srt = np.sort(np.where(valid, ids, -1 - np.arange(k)[None, :]), 1)
    dup = (srt[:, 1:] == srt[:, :-1]).any(1)
    short = n_ret < np.minimum(k, n_match)
    empty = (n_ret == 0) & (n_match >= GRAPH_SERVES * n) & (n_match > 0)
    brute = np.asarray(ans_brute, bool)
    short_brute = short & brute
    safe = np.clip(ids, 0, n - 1)
    fails = np.zeros(len(ids), bool)
    for fi in np.unique(pool.filter_of[ans_items]):
        rows = np.nonzero(pool.filter_of[ans_items] == fi)[0]
        ok = eval_filter(pool.filters[fi], ints[safe[rows].ravel()],
                         floats[safe[rows].ravel()], cols).reshape(-1, k)
        fails[rows] = (valid[rows] & ~ok).any(1)
    dv = np.where(valid, d, np.inf)
    with np.errstate(invalid="ignore"):          # inf - inf past the last id
        unsorted = (~np.isfinite(np.where(valid, d, 0.0))).any(1) | \
            (np.diff(dv, axis=1) < 0).any(1)

    # true distance of every returned row, exactly (difference form)
    err_sum, n_rows = 0.0, 0
    kth = np.where(np.isfinite(ref_d), ref_d, 0.0).max(1)
    far = np.zeros(len(ids), bool)
    for s in range(0, len(ids), 4096):
        sl = slice(s, s + 4096)
        q = pool.queries[ans_items[sl]].astype(np.float64)
        diff = vecs[safe[sl]].astype(np.float64) - q[:, None, :]
        true_d = np.sqrt(np.einsum("akd,akd->ak", diff, diff))
        ok = valid[sl] & ~oor[sl, None]
        gap = np.abs(np.where(ok, d[sl] - true_d, 0.0))
        gap = np.where(np.isfinite(gap), gap, 1.0)
        err_sum += float((gap / np.maximum(kth[sl], 1e-30)[:, None]).sum())
        n_rows += int(ok.sum())
        far[sl] = (ok & (true_d > kth[sl, None] * (1.0 + TIE))).any(1)
    far_brute = far & brute
    bad = oor | dup | fails | unsorted | empty | short_brute
    if brute_exact:
        bad |= far_brute
    recall = recall_at_k(ids, ref_ids, k)

    def miss(rows: np.ndarray) -> float:
        return float(1.0 - recall[rows].mean()) if rows.any() else 0.0

    numbers = {"unanswered": int(n_unanswered), "bad_answers": int(bad.sum()),
               "recall_miss": miss(~brute),
               "dist_err": err_sum / max(n_rows, 1)}
    if not brute_exact:
        numbers["brute_recall_miss"] = miss(brute)
    diag = {"answers": int(len(ids)), "graph_answers": int((~brute).sum()),
            "recall": float(recall.mean()) if len(ids) else float("nan"),
            "out_of_range": int(oor.sum()), "duplicate": int(dup.sum()),
            "short": int(short.sum()), "short_brute": int(short_brute.sum()),
            "far_brute": int(far_brute.sum()),
            "empty": int(empty.sum()), "filter_fail": int(fails.sum()),
            "unsorted": int(unsorted.sum())}
    return numbers, diag


def judge(numbers: dict, limits: dict) -> bool:
    missing = sorted(set(numbers) - set(limits))
    if missing:
        raise KeyError(f"the cell's limits give no limit for {missing}")
    return all(numbers[name] <= limits[name] for name in numbers)


# ---------------------------------------------------------------------------
# Control: the reference one precision step down
# ---------------------------------------------------------------------------
def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to bfloat16 (nearest even), returned as float32."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32)
    r = (b + np.uint32(0x7FFF) + ((b >> 16) & np.uint32(1))) & np.uint32(0xFFFF0000)
    return r.view(np.float32)


def dot_high(q: np.ndarray, x: np.ndarray) -> np.ndarray:
    """q @ x.T at ``Precision.HIGH``: three bfloat16 products, each exact in
    float32, accumulated in float32."""
    qh = _bf16(q)
    ql = _bf16(q - qh)
    xh = _bf16(x)
    xl = _bf16(x - xh)
    return (qh @ xh.T) + (qh @ xl.T) + (ql @ xh.T)


def control_answers(vecs, ints, floats, cols, pool, k: int,
                    items: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """What the reference answers when it scores like the program (float32
    norms, d = sqrt(|x|^2 + |q|^2 - 2 q.x)) with its dot at ``HIGH``."""
    x = np.ascontiguousarray(vecs, np.float32)
    xn = np.einsum("nd,nd->n", x, x)
    ids = np.full((len(items), k), -1, np.int64)
    dist = np.full((len(items), k), np.inf, np.float32)
    for s in range(0, len(items), BLOCK):
        blk = items[s:s + BLOCK]
        q = pool.queries[blk].astype(np.float32)
        qn = np.einsum("bd,bd->b", q, q)
        d2 = np.maximum(xn[None, :] + qn[:, None] - 2.0 * dot_high(q, x), 0.0)
        for r, item in enumerate(blk):
            fi = int(pool.filter_of[item])
            row = np.where(eval_filter(pool.filters[fi], ints, floats, cols),
                           d2[r], np.inf)
            kk = min(k, int(np.isfinite(row).sum()))
            if kk == 0:
                continue
            part = np.argpartition(row, kk - 1)[:kk]
            part = part[np.argsort(row[part], kind="stable")]
            ids[s + r, :kk] = part
            dist[s + r, :kk] = np.sqrt(row[part])
    return ids, dist
