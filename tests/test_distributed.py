"""Distributed behaviour — runs in a subprocess with 8 host devices so the
main pytest process keeps its single-device backend."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_multi_device(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_sharded_search_matches_oracle():
    out = _run_multi_device("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import make_sharded_search, shard_database
        from repro.kernels import ref
        from repro.data.molecules import SyntheticConfig, synthetic_fingerprints, queries_from_db
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        db = synthetic_fingerprints(SyntheticConfig(n=4000, seed=0))
        q = jnp.asarray(queries_from_db(db, 8))
        with mesh:
            db_s, cnt_s, n = shard_database(mesh, db)
            search, _, _ = make_sharded_search(mesh, db_s.shape[0], 10)
            vals, ids = search(q, db_s, cnt_s)
        rids, rvals = ref.tanimoto_topk_ref(q, jnp.asarray(db), 10)
        np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals), rtol=1e-6)
        s = np.asarray(ref.tanimoto_scores_ref(q, jnp.asarray(db)))
        got = s[np.arange(8)[:, None], np.asarray(ids)]
        np.testing.assert_allclose(got, np.asarray(rvals), rtol=1e-6)
        print("SHARDED_OK")
    """)
    assert "SHARDED_OK" in out


def test_sharded_search_multipod_hierarchical_merge():
    out = _run_multi_device("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import make_sharded_search, shard_database
        from repro.kernels import ref
        from repro.data.molecules import SyntheticConfig, synthetic_fingerprints, queries_from_db
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        db = synthetic_fingerprints(SyntheticConfig(n=2048, seed=1))
        q = jnp.asarray(queries_from_db(db, 4))
        with mesh:
            db_s, cnt_s, n = shard_database(mesh, db)
            search, _, _ = make_sharded_search(mesh, db_s.shape[0], 5)
            vals, ids = search(q, db_s, cnt_s)
        _, rvals = ref.tanimoto_topk_ref(q, jnp.asarray(db), 5)
        np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals), rtol=1e-6)
        print("MULTIPOD_OK")
    """)
    assert "MULTIPOD_OK" in out


def test_sharded_search_masks_pad_rows():
    """Regression (ISSUE 3 satellite): `shard_database` pads the DB with
    zero rows to the shard multiple; without masking their 0-score entries
    surface in the merged top-k once k approaches the shard size. With
    ``n_valid`` threaded through, pad ids come back as -1 / sim 0."""
    out = _run_multi_device("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import make_sharded_search, shard_database
        from repro.launch.mesh import make_mesh
        from repro.data.molecules import SyntheticConfig, synthetic_fingerprints
        mesh = make_mesh((4, 2), ("data", "model"))
        db = synthetic_fingerprints(SyntheticConfig(n=10, seed=0))  # pads to 12
        with mesh:
            db_s, cnt_s, n_valid = shard_database(mesh, db)
            assert db_s.shape[0] == 12 and n_valid == 10
            # k == padded total: every row (incl. both pads) is a candidate
            search, _, _ = make_sharded_search(mesh, db_s.shape[0], 12,
                                               n_valid=n_valid)
            q = jnp.asarray(db[:3])
            vals, ids = search(q, db_s, cnt_s)
        ids, vals = np.asarray(ids), np.asarray(vals)
        assert (ids < n_valid).all(), ids          # no pad id ever surfaces
        assert ((ids >= 0).sum(axis=1) == n_valid).all(), ids
        assert (vals[ids < 0] == 0.0).all()
        # the valid entries are exactly the 10 real rows
        for row in ids:
            assert set(int(i) for i in row if i >= 0) == set(range(10))
        print("PAD_MASK_OK")
    """)
    assert "PAD_MASK_OK" in out


def test_quantized_psum_close_to_exact():
    out = _run_multi_device("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import quantized_psum
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        x = jax.random.normal(jax.random.key(0), (8, 512))
        f_q = shard_map(lambda v: quantized_psum(v[0], "data"), mesh=mesh,
                        in_specs=P("data"), out_specs=P(), check_vma=False)
        f_e = shard_map(lambda v: jax.lax.psum(v[0], "data"), mesh=mesh,
                        in_specs=P("data"), out_specs=P(), check_vma=False)
        got, exact = f_q(x), f_e(x)
        err = float(jnp.abs(got - exact).max())
        scale = float(jnp.abs(x).max())
        assert err < scale * 8 / 127 + 1e-5, (err, scale)
        print("PSUM_OK", err)
    """)
    assert "PSUM_OK" in out


def test_production_mesh_shapes():
    out = _run_multi_device("""
        import os
        import jax
        from repro.launch.mesh import make_production_mesh, data_axes
        m = make_production_mesh()
        assert m.devices.shape == (16, 16) and m.axis_names == ("data", "model")
        mp = make_production_mesh(multi_pod=True)
        assert mp.devices.shape == (2, 16, 16)
        assert mp.axis_names == ("pod", "data", "model")
        assert data_axes(mp) == ("pod", "data")
        print("MESH_OK")
    """, n_devices=512)
    assert "MESH_OK" in out


def test_train_step_runs_on_local_mesh():
    out = _run_multi_device("""
        from repro.launch.train import train
        losses = train("granite-3-2b", steps=3, global_batch=8, seq_len=32,
                       ckpt_dir="/tmp/repro_test_dist_ckpt", ckpt_every=0,
                       log=lambda *a: None)
        assert len(losses) == 3
        print("TRAIN_OK", losses[-1])
    """)
    assert "TRAIN_OK" in out
