"""Device idle time by the program layer that held it.

A traced run's idle stretches (``tracereduce.gaps``: the parts of
``bench.traced_window`` in which the device ran nothing) are laid against
the program's own spans (``src/repro/obs/trace.py``), which share the
device trace's clock. Each stretch between span edges goes to one bucket:

- the layer of the innermost program span open over it. Spans of several
  threads can be open at once (a batch runs on the replica's worker while
  the dispatcher ticks), so the deepest layer of the served path wins
  (engine, then service, then front end), and the latest started among
  spans of one layer, which on one thread is the innermost. JAX's and the
  runtime's own spans (``np.asarray``, allocator, transfers) are skipped,
  so the program's layer decides;
- ``unspanned``: no program span is open while the benchmark waits on a
  served call (``bench.batch``, ``bench.await_answer``): the call is in
  program code with no span, or between threads;
- ``bench``: only another of the benchmark's own spans is open;
- ``none``: nothing is open.

The buckets add up to the idle time ``device.idle_share.*`` reads. A trace
holding no program span at all, from a program that does not span its
layers, gives no reading.
"""
from __future__ import annotations

from bench import tracereduce

#: bucket, the name prefixes it takes, its depth in the served path
LAYERS = (
    ("engine.fetch", ("engine.fetch",), 3),
    ("engine.host", ("engine.", "tier.", "hnsw."), 3),
    ("service", ("service.", "wal.", "snapshot."), 2),
    ("frontend", ("frontend.", "replica."), 1),
)
AWAIT = ("bench.batch", "bench.await_answer")
BUCKETS = tuple(b for b, _, _ in LAYERS) + ("unspanned", "bench", "none")


def layer(name: str):
    """(bucket, depth) of a program span's name, or None."""
    for bucket, prefixes, depth in LAYERS:
        if name.startswith(prefixes):
            return bucket, depth
    return None


def idle_by_layer(trace) -> dict | None:
    """Idle seconds of the traced window by bucket (``BUCKETS``), or None
    where the trace holds no program span. One sweep over span edges."""
    spans = []
    for name, s, e in trace.host:
        if name == tracereduce.WINDOW_SPAN:
            continue
        lay = layer(name)
        if lay is not None:
            spans.append((s, e, lay))
        elif name.startswith(AWAIT):
            spans.append((s, e, ("unspanned", 0)))
        elif name.startswith("bench."):
            spans.append((s, e, ("bench", -1)))
    if not any(lay[1] > 0 for _, _, lay in spans):
        return None
    edges = sorted((t, opening, i) for i, (s, e, _) in enumerate(spans)
                   for t, opening in ((s, 1), (e, 0)))
    out = dict.fromkeys(BUCKETS, 0.0)
    active: set = set()
    k = 0

    def bucket() -> str:
        best = max(((spans[i][2][1], spans[i][0], spans[i][2][0])
                    for i in active), default=None)
        return best[2] if best else "none"

    for lo, hi in tracereduce.gaps(trace):
        while k < len(edges) and edges[k][0] <= lo:
            _, opening, i = edges[k]
            (active.add if opening else active.discard)(i)
            k += 1
        t0 = lo
        while k < len(edges) and edges[k][0] < hi:
            t, opening, i = edges[k]
            out[bucket()] += t - t0
            (active.add if opening else active.discard)(i)
            t0 = t
            k += 1
        out[bucket()] += hi - t0
    return out


def share(trace, which: str) -> float | None:
    """Bucket ``which``'s idle time as a share of the traced window, in %."""
    if trace is None:
        return None
    idle = idle_by_layer(trace)
    if idle is None:
        return None
    lo, hi = trace.window
    return 100.0 * idle[which] / (hi - lo)
