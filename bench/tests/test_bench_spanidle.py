"""Device idle time by the program layer that held it, on synthetic traces
and on the trace recorded on the chip before the program spanned its
layers (``bench/tests/data``)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spanidle, spec, tracereduce  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
CELLS = ("chembl27-bitbound.screen", "chembl27-exact.screen")
READERS = {"frontend.idle_share.screen": "frontend",
           "service.idle_share.screen": "service",
           "engine.host_idle_share.screen": "engine.host",
           "engine.fetch_idle_share.screen": "engine.fetch",
           "device.idle_unspanned_share.screen": "unspanned"}


def served_batch() -> tracereduce.Trace:
    """One closed-loop batch: the device busy over [0, 2] and [5, 6] of a
    10 s window; the benchmark's call open over [1, 9]; the dispatcher's
    tick on its own thread while the worker runs the replica's task."""
    return tracereduce.Trace(
        window=(0.0, 10.0),
        device={"/device:TPU:0": [("fusion.1", -1.0, 2.0),
                                  ("_window_topk_impl.3", 5.0, 6.0)]},
        host=[("bench.batch", 1.0, 9.0),
              ("frontend.admit", 1.0, 1.5),
              ("frontend.tick", 2.0, 2.5),
              ("replica.task", 2.2, 4.8),
              ("service.batch", 2.3, 4.7),
              ("engine.plan", 2.4, 2.6),
              ("engine.sync", 2.45, 2.55),
              ("engine.dispatch", 2.6, 2.8),
              ("engine.fetch", 4.0, 4.6),
              ("np.asarray_jax.Array_", 4.1, 4.5),   # JAX's own: skipped
              ("frontend.tick", 4.2, 4.3),           # later, but shallower
              ("service.pad", 4.6, 4.7),
              ("bench.draw", 9.2, 9.6)])


EXPECTED = {"frontend": 0.4, "service": 1.4, "engine.host": 0.4,
            "engine.fetch": 0.6, "unspanned": 3.2, "bench": 0.4,
            "none": 0.6}


def test_each_idle_stretch_goes_to_the_deepest_program_layer():
    t = served_batch()
    assert tracereduce.gaps(t) == [(2.0, 5.0), (6.0, 10.0)]
    assert spanidle.idle_by_layer(t) == pytest.approx(EXPECTED)
    assert set(spanidle.BUCKETS) == set(EXPECTED)


def test_span_names_map_to_layers():
    assert spanidle.layer("engine.fetch") == ("engine.fetch", 3)
    assert spanidle.layer("engine.plan") == ("engine.host", 3)
    assert spanidle.layer("tier.stall") == ("engine.host", 3)
    assert spanidle.layer("hnsw.search") == ("engine.host", 3)
    assert spanidle.layer("wal.fsync") == ("service", 2)
    assert spanidle.layer("snapshot.write") == ("service", 2)
    assert spanidle.layer("replica.task") == ("frontend", 1)
    assert spanidle.layer("bench.batch") is None
    assert spanidle.layer("D2H_Dispatch") is None


def test_the_readers_add_up_to_the_device_idle_share():
    t = served_batch()

    class Run:
        trace = t
    for cell in CELLS:
        c = spec.resolve(cell)
        names = {m["name"] for m in c.per_layer}
        assert set(READERS) <= names
        got = {n: spec.metric_reader(c, n)(Run) for n in READERS}
        for name, bucket in READERS.items():
            assert got[name] == pytest.approx(10.0 * EXPECTED[bucket])
        idle = spec.metric_reader(c, "device.idle_share.screen")(Run)
        rest = 10.0 * (EXPECTED["bench"] + EXPECTED["none"])
        assert sum(got.values()) + rest == pytest.approx(idle)
        assert idle == pytest.approx(70.0)


def test_no_reading_without_program_spans():
    """A program that does not span its layers, as before, gives no reading
    (not a zero), and neither does an untraced run."""
    t = served_batch()
    t.host = [s for s in t.host if s[0].startswith(("bench.", "np."))]
    assert spanidle.idle_by_layer(t) is None
    for bucket in READERS.values():
        assert spanidle.share(t, bucket) is None
        assert spanidle.share(None, bucket) is None


def test_the_trace_recorded_on_the_chip_before_the_spans():
    """Two ``chembl27-bitbound.screen`` batches on one TPU v5e, recorded by
    a program with no spans of its own: nothing to read."""
    t = tracereduce.load(DATA)
    assert spanidle.idle_by_layer(t) is None
