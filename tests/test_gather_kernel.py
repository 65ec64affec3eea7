"""Gather-distance kernel (kernels/gather.py) vs the pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.molecules import SyntheticConfig, synthetic_fingerprints
from repro.kernels import ops, ref


def _db(n, seed=0):
    return jnp.asarray(synthetic_fingerprints(SyntheticConfig(n=n, seed=seed)))


@pytest.mark.parametrize("n,q,e,seed", [
    (400, 3, 8, 0),
    (1000, 2, 32, 1),     # beam-sized expansion (B*2M)
    (257, 1, 5, 2),       # odd shapes
])
def test_gather_matches_oracle(n, q, e, seed):
    db = _db(n, seed)
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, n, size=(q, e)).astype(np.int32)
    # sprinkle invalid ids (masked/visited/padded neighbours)
    ids[rng.random(ids.shape) < 0.3] = -1
    got = ops.gather_tanimoto(db[:q], ops.db_lines(db), jnp.asarray(ids))
    want = ref.gather_tanimoto_ref(db[:q], db, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_gather_all_invalid_row():
    """A fully masked query row (all -1) must come back all -inf."""
    db = _db(300)
    ids = np.full((2, 6), -1, np.int32)
    ids[1, 0] = 7
    got = np.asarray(ops.gather_tanimoto(db[:2], ops.db_lines(db),
                                         jnp.asarray(ids)))
    assert not np.isfinite(got[0]).any()
    assert np.isfinite(got[1, 0]) and not np.isfinite(got[1, 1:]).any()


def test_gather_self_id_scores_one():
    db = _db(300)
    ids = np.arange(4, dtype=np.int32)[:, None]
    got = np.asarray(ops.gather_tanimoto(db[:4], ops.db_lines(db),
                                         jnp.asarray(ids)))
    np.testing.assert_allclose(got[:, 0], 1.0, rtol=1e-6)


def test_gather_inside_jitted_loop():
    """The traversal launches the kernel from inside lax.while_loop — the
    kernel must trace there (ids are loop-carried traced values)."""
    db = _db(200)
    q = db[:3]
    lines = ops.db_lines(db)

    def f(ids0):
        def body(carry):
            i, ids, acc = carry
            s = ops.gather_tanimoto(q, lines, ids)
            acc = acc + jnp.where(jnp.isfinite(s), s, 0.0).sum()
            return i + 1, (ids + 1) % 200, acc

        return jax.lax.while_loop(lambda c: c[0] < 3, body,
                                  (0, ids0, jnp.float32(0)))[2]

    ids0 = jnp.arange(6, dtype=jnp.int32).reshape(3, 2)
    out = jax.jit(f)(ids0)
    assert np.isfinite(float(out)) and float(out) > 0
