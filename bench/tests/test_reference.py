"""The float32 references against the program's own forward, at a tiny
size on the CPU, with float32 weights: same seed, same equations."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from reference import llama, mamba2

TINY = {
    "llama": {"hidden_size": 128, "num_attention_heads": 4,
              "num_key_value_heads": 2, "intermediate_size": 256,
              "num_hidden_layers": 2, "vocab_size": 512,
              "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
              "torch_dtype": "float32"},
    "mamba2": {"d_model": 128, "n_layer": 2, "vocab_size": 512,
               "d_state": 16, "d_conv": 4, "expand": 2, "headdim": 32,
               "chunk_size": 32, "norm_epsilon": 1e-5,
               "torch_dtype": "float32"},
}


def program_logits(family, cfg, seed, tokens):
    from adapter import arch_config
    from repro.models import model as M
    from repro.models.transformer import lm_forward
    arch = arch_config({"family": family, "program_name": f"tiny-{family}",
                        "config": cfg})
    params = M.init_params(arch, jax.random.PRNGKey(seed))
    with jax.default_matmul_precision("highest"):
        logits, _ = lm_forward(params, tokens, arch)
    return np.asarray(logits[..., :cfg["vocab_size"]], np.float32)


@pytest.mark.parametrize("family", ["llama", "mamba2"])
def test_reference_matches_program(family):
    cfg = TINY[family]
    ref = {"llama": llama, "mamba2": mamba2}[family]
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 96), 0,
                                cfg["vocab_size"])
    want = program_logits(family, cfg, 1234, tokens)
    got = np.asarray(ref.logits(cfg, 1234, tokens, start=0))
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-4, err


@pytest.mark.parametrize("family", ["llama", "mamba2"])
def test_fp8_control_departs(family):
    """The control's logits lie measurably off the float32 ones."""
    cfg = TINY[family]
    ref = {"llama": llama, "mamba2": mamba2}[family]
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0,
                                cfg["vocab_size"])
    hi = np.asarray(ref.logits(cfg, 99, tokens, start=32))
    lo = np.asarray(ref.logits(cfg, 99, tokens, start=32, low=True))
    assert np.abs(lo - hi).max() > 1e-3 * np.abs(hi).max()
