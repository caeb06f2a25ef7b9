"""The in-place SSD decode kernel (kernels/ssd_decode.py) against the
jnp state math of ``models/ssm.py::ssd_decode_step``, in interpret
mode: the updated layer and the readout agree at float32 precision,
and every other layer of the stack is left bit for bit as it was."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops


def _inputs(seed, L, B, H, N, P):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    state = jax.random.normal(ks[0], (L, B, H, N, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H), jnp.float32))
    A = jnp.exp(jax.random.uniform(ks[2], (H,), jnp.float32, 0.0, 2.8))
    dA = jnp.exp(-dt * A)
    x = jax.random.normal(ks[3], (B, H, P), jnp.float32)
    Bm = jax.random.normal(ks[4], (B, N), jnp.float32)
    Cm = jax.random.normal(ks[5], (B, N), jnp.float32)
    return state, dA, dt, x, Bm, Cm


def _reference(h, dA, dt, x, Bm, Cm):
    """The state update and readout of ``ssd_decode_step``'s jnp path."""
    hn = h * dA[:, :, None, None] + jnp.einsum("bh,bn,bhp->bhnp", dt, Bm, x)
    return jnp.einsum("bn,bhnp->bhp", Cm, hn), hn


@pytest.mark.parametrize("B", [1, 4])
@pytest.mark.parametrize("H", [8, 12, 24, 32])
def test_kernel_matches_jnp_update_in_place(B, H):
    """H = 24 runs three blocks of 8 heads and 32 two of 16; 12 is no
    multiple of the 8-head block, so the block spans all 12 heads."""
    L, N, P, layer = 3, 16, 32, 1
    state, dA, dt, x, Bm, Cm = _inputs(B * 100 + H, L, B, H, N, P)
    want_y, want_h = _reference(state[layer], dA, dt, x, Bm, Cm)
    y, new = ops.ssd_decode_inplace(state, jnp.int32(layer), dA, dt, x, Bm,
                                    Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new[layer]), np.asarray(want_h),
                               rtol=1e-5, atol=1e-6)
    for other in range(L):
        if other != layer:
            np.testing.assert_array_equal(np.asarray(new[other]),
                                          np.asarray(state[other]))


def test_kernel_at_published_head_tile():
    """mamba2-370m's head tile, (N, P) = (128, 64), at 32 heads: the
    first and the last layer of the stack."""
    L, B, H, N, P = 2, 1, 32, 128, 64
    state, dA, dt, x, Bm, Cm = _inputs(7, L, B, H, N, P)
    for layer in (0, L - 1):
        want_y, want_h = _reference(state[layer], dA, dt, x, Bm, Cm)
        y, new = ops.ssd_decode_inplace(state, jnp.int32(layer), dA, dt, x,
                                        Bm, Cm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(new[layer]),
                                   np.asarray(want_h), rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(new[L - 1 - layer]),
                                      np.asarray(state[L - 1 - layer]))


@pytest.mark.parametrize("heads,want", [
    (32, 16), (48, 16), (24, 8), (12, 12), (8, 8), (4, 4)])
def test_block_heads_divides_the_head_axis(heads, want):
    assert ops.ssd_decode_block_heads(heads) == want
