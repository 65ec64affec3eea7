"""Compile rehearsal for TPU v5e: every Pallas kernel of the search path and
the jitted exhaustive-engine pipelines, at the paper deployment's real
widths (CHEMBL_LIKE: N=1,941,405 x 1024-bit, k=20, Sc=0.8, m=4, HNSW M=16),
lowered with ``interpret=False`` for a described (not attached) v5e chip.

Nothing runs: this catches what interpret mode cannot — block shapes that
break the (8, 128) tiling rule, primitives Mosaic cannot lower, VMEM
overflows and programs that do not fit the chip's 16 GB.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU compiler library, so a worker that
imported this file must not grab it unless it runs these tests.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper import CHEMBL_LIKE
from repro.core import folding as fl
from repro.kernels import ops

N = CHEMBL_LIKE.n_molecules
W = CHEMBL_LIKE.fp_len // 32
K = CHEMBL_LIKE.k
CAP = 1 << (N - 1).bit_length()          # the store's power-of-two capacity
HBM_BYTES = 16 * 10**9                   # v5e: 16 GB per chip
HNSW_N = 20_000                          # served HNSW size (graph build cap)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — skip wherever it cannot be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """The wrappers ask the default backend (CPU here) whether to interpret;
    the rehearsal compiles the Mosaic path instead."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _spec(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("q_n", [8, 64])
def test_fused_topk_compiles(one_chip, mosaic, q_n):
    _compile(lambda q, db, c: ops.tanimoto_topk(q, db, K, db_popcount=c),
             _spec(one_chip, (q_n, W), jnp.uint32),
             _spec(one_chip, (N, W), jnp.uint32),
             _spec(one_chip, (N,), jnp.int32))


@pytest.mark.parametrize("q_n", [8, 64])
def test_blocked_topk_compiles(one_chip, mosaic, q_n):
    """A 16-query block: two sublane tiles per DB sweep."""
    _compile(lambda q, db, c: ops.tanimoto_topk(q, db, K, db_popcount=c,
                                                qb=16),
             _spec(one_chip, (q_n, W), jnp.uint32),
             _spec(one_chip, (N, W), jnp.uint32),
             _spec(one_chip, (N,), jnp.int32))


@pytest.mark.parametrize("q_n", [8, 64])
def test_bitbound_window_compiles(one_chip, mosaic, q_n):
    """BitBound over the full-resolution popcount-sorted DB: the row-window
    kernel over the rows the Eq.2 popcount window selects."""
    _compile(lambda q, db, c, lo, hi: ops.window_topk(q, db, c, lo, hi, K),
             _spec(one_chip, (q_n, W), jnp.uint32),
             _spec(one_chip, (N, W), jnp.uint32),
             _spec(one_chip, (N,), jnp.int32),
             _spec(one_chip, (q_n,), jnp.int32),
             _spec(one_chip, (q_n,), jnp.int32))


@pytest.mark.parametrize("q_n", [1, 20, 8, 64, 1024])
def test_stage1_window_compiles(one_chip, mosaic, q_n):
    """Stage 1 of the BitBound+folding engine: the folded (m=4) capacity-
    padded main segment, k1 = k*m*log2(2m) candidates, a half-DB window
    (512 tiles of 2048). A batch of up to 32 is one block of its own size
    (1: a served query; 20: not a multiple of the sublane tile); 1024
    queries is the screen cell's batch, 32 blocks of 32."""
    m = CHEMBL_LIKE.folding_m
    k1 = fl.kr1_for(K, m)
    wf = fl.folded_words(W, m)
    _compile(lambda q, db, c, lo, hi: ops.window_topk(
                 q, db, c, lo, hi, k1, max_tiles=CAP // 2048 // 2),
             _spec(one_chip, (q_n, wf), jnp.uint32),
             _spec(one_chip, (CAP, wf), jnp.uint32),
             _spec(one_chip, (CAP,), jnp.int32),
             _spec(one_chip, (q_n,), jnp.int32),
             _spec(one_chip, (q_n,), jnp.int32))


def test_bitcount_compiles(one_chip, mosaic):
    _compile(ops.bitcount, _spec(one_chip, (N, W), jnp.uint32))


@pytest.mark.parametrize("q_n", [8, 64])
def test_gather_compiles(one_chip, mosaic, q_n):
    """One beam expansion of the rows layout (beam 4 x 2M = 128 ids)."""
    e = 4 * 2 * CHEMBL_LIKE.hnsw_m
    _compile(ops.gather_tanimoto,
             _spec(one_chip, (q_n, W), jnp.uint32),
             _spec(one_chip, ops.db_lines(np.zeros((N, W), np.uint32)).shape,
                   jnp.uint32),
             _spec(one_chip, (q_n, e), jnp.int32))


@pytest.mark.parametrize("q_n", [8, 64])
def test_expand_compiles(one_chip, mosaic, q_n):
    """The blocked layout's fused expansion, at the served HNSW size."""
    m2, beam = 2 * CHEMBL_LIKE.hnsw_m, 4
    _compile(lambda q, nbr, pop, flat, worst: ops.expand_tanimoto_sorted(
                 q, nbr, pop, flat, worst, CHEMBL_LIKE.hnsw_ef_search),
             _spec(one_chip, (q_n, W), jnp.uint32),
             _spec(one_chip, ops.nbr_slabs(np.zeros((HNSW_N, m2, W),
                                                    np.uint32)).shape,
                   jnp.uint32),
             _spec(one_chip, (q_n, beam), jnp.int32),
             _spec(one_chip, (q_n, beam * m2), jnp.int32),
             _spec(one_chip, (q_n,), jnp.float32))


def _fits(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, mem
    return total


def test_mesh_search_compiles(topo, mosaic):
    """The exhaustive mesh scan over the host's four chips (4x1 mesh): the
    fused kernel per shard, then the all_gather merge; per-chip memory
    fits."""
    from jax.sharding import Mesh, NamedSharding
    from repro.core.distributed import make_sharded_search
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"))
    n_total = -(-N // 4) * 4
    fn, db_spec, cnt_spec = make_sharded_search(mesh, n_total, K,
                                                use_kernel=True, n_valid=N)
    compiled = _compile(
        fn, _spec(NamedSharding(mesh, jax.sharding.PartitionSpec()),
                  (64, W), jnp.uint32),
        _spec(NamedSharding(mesh, db_spec), (n_total, W), jnp.uint32),
        _spec(NamedSharding(mesh, cnt_spec), (n_total,), jnp.int32))
    assert "all-gather" in compiled.as_text()
    _fits(compiled)


def test_brute_engine_pipeline_fits(one_chip, mosaic):
    """The served BruteForceEngine main-segment pipeline at Q=64."""
    from repro.core import BruteForceEngine
    eng = BruteForceEngine(np.zeros((256, W), np.uint32), backend="tpu")
    fn = eng._main_builder(K)()
    _fits(_compile(fn, _spec(one_chip, (64, W), jnp.uint32),
                   _spec(one_chip, (CAP, W), jnp.uint32),
                   _spec(one_chip, (CAP,), jnp.int32)))


def test_bitbound_engine_pipeline_fits(one_chip, mosaic):
    """The served BitBoundFoldingEngine two-stage pipeline at Q=64, full
    capacity and the worst-case (whole-DB) window bucket."""
    from repro.core import BitBoundFoldingEngine
    m = CHEMBL_LIKE.folding_m
    eng = BitBoundFoldingEngine(np.zeros((256, W), np.uint32),
                                cutoff=CHEMBL_LIKE.cutoff, m=m, backend="tpu")
    total_tiles = CAP // 2048
    eng._device_meta = lambda: {"kops": ops, "tile": 2048,
                                "total_tiles": total_tiles, "capacity": CAP}
    fn = eng._build_device_search(total_tiles, K, 0)
    wf = fl.folded_words(W, m)
    _fits(_compile(fn, _spec(one_chip, (64, W), jnp.uint32),
                   _spec(one_chip, (64,), jnp.int32),
                   _spec(one_chip, (64,), jnp.int32),
                   _spec(one_chip, (64,), jnp.int32),
                   _spec(one_chip, (CAP, wf), jnp.uint32),
                   _spec(one_chip, (CAP,), jnp.int32),
                   _spec(one_chip, (CAP, W), jnp.uint32),
                   _spec(one_chip, (CAP,), jnp.int32),
                   _spec(one_chip, (CAP,), jnp.int32)))



@pytest.mark.parametrize("layout", ["rows", "blocked"])
def test_hnsw_engine_pipeline_fits(one_chip, mosaic, layout):
    """The served HNSWEngine traversal at Q=64 over a graph of the full
    library's capacity. The kernels read the graph's own line and slab
    layouts, so no launch relays the database out: the blocked layout's
    2M-neighbour slabs (8 GiB at this size) fit the chip only because no
    per-call copy of them is made."""
    from repro.core import HNSWEngine
    small = np.random.default_rng(0).integers(0, 2**32, (300, W),
                                              dtype=np.uint32)
    eng = HNSWEngine(small, m=CHEMBL_LIKE.hnsw_m, ef_construction=20,
                     ef_search=CHEMBL_LIKE.hnsw_ef_search, backend="tpu",
                     layout=layout)
    fn, (q, graph) = eng.device_pipeline(small[:64], K)
    n0 = graph.db_popcount.shape[0]

    def at_capacity(x):                  # node axis n0 -> CAP, lines n0/4
        shape = list(x.shape)
        if x is graph.db_lines:
            shape[0] = CAP // 4
        elif x is graph.upper_adj:
            shape[1] = CAP
        elif x.ndim:
            assert shape[0] == n0, (shape, n0)
            shape[0] = CAP
        return _spec(one_chip, tuple(shape), x.dtype)

    _fits(_compile(fn, _spec(one_chip, q.shape, q.dtype),
                   jax.tree.map(at_capacity, graph)))
