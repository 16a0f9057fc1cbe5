"""Kernel: ``pq_adc_topr`` (the compressed brute route's filtered ADC scan)
against its roofline.

Work the scan needs, whatever implements it, for ``b`` real brute rows
(``favor_requests_total{route="brute"}``) over ``c`` calls of the brute
program (``pq_prefbf_topk``) on an ``N``-row corpus of ``m`` sub-quantiser
codes with ``K`` centroids each:

* operations: one table add per sub-quantiser, row and query, counted as
  two (a lookup and an add): ``2 b N m``;
* bytes: each call reads every row's codes, norm and attributes once,
  ``c N (m + 4 + attribute bytes)``; each query row reads its float32
  lookup table and writes its ``R = rerank * k`` candidates (id and
  distance), ``b (m K 4 + 8 R)``.

Time: device seconds of the Pallas kernel ``favor.pq_adc_topr``."""
from layer import attr_bytes, roofline


def scan_ops(b: float, n: int, m: int) -> float:
    return 2.0 * b * n * m


def scan_bytes(c: float, b: float, n: int, m: int, ksub: int, r: int,
               attr: int) -> float:
    return c * n * (m + 4 + attr) + b * (m * ksub * 4 + 8 * r)


def candidates(cfg: dict) -> int:
    """R: the ADC candidates the scan keeps for the exact re-rank."""
    k = cfg["search"]["k"]
    rerank = cfg["search"].get("rerank")
    if rerank is None:
        rerank = cfg["quant"].get("rerank", 4)
    return max(k, rerank * k)


def read(ctx):
    q = ctx.cfg.get("quant") or {}
    if q.get("kind") != "pq" or not ctx.cfg["search"].get("use_pq"):
        return None
    b = ctx.counter("favor_requests_total", 'route="brute"')
    c = ctx.module_calls("pq_prefbf_topk")
    n, m, ksub = ctx.cfg["corpus"]["n"], q["m"], 1 << q["nbits"]
    return roofline(ctx, "pq_adc_topr_roofline", ctx.kernel_s("pq_adc_topr"),
                    ops=scan_ops(b, n, m),
                    nbytes=scan_bytes(c, b, n, m, ksub, candidates(ctx.cfg),
                                      attr_bytes(ctx.cfg)))
