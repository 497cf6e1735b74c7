"""The plain reference makes the configuration's weights from the seed as
the configuration states them, and agrees with the program's float32
forward pass at a tiny width."""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import cells as C
from chipbench.configs import dense_reference as ref
from conftest import tiny_cell


def test_weights_follow_the_seeded_scheme():
    from repro.models import transformer as T
    cell = tiny_cell()
    cfg = C.program_config(cell.config)
    key = jax.random.PRNGKey(2 ** 31 + 9)
    p = T.init_params(key, cfg, jnp.bfloat16)
    dm = ref.Dims(dict(cell.config, dtype="bfloat16"))
    k_embed, k_layers, k_head = ref.top_keys(key, dm.n)
    w = ref._layer_weights(k_layers[1], dm)
    seg = p["segments"][0]
    np.testing.assert_array_equal(
        w["wq"], np.asarray(seg["attn"]["wq"][1], np.float32))
    np.testing.assert_array_equal(
        w["wd"], np.asarray(seg["mlp"]["wd"][1], np.float32))
    np.testing.assert_array_equal(
        np.asarray(ref._embed(k_embed, jnp.arange(5), dm)),
        np.asarray(p["embed"][:5], np.float32))


def test_logits_agree_with_the_program_in_float32():
    from repro.models import transformer as T
    cell = tiny_cell()
    cfg = C.program_config(cell.config)
    key = jax.random.PRNGKey(7)
    toks = np.random.default_rng(0).integers(2, 256, size=(2, 24))
    ctx = T.ParallelCtx(remat=False, q_block=8, kv_block=8)
    params = T.init_params(key, cfg, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(T.prefill_logits(
            params, jnp.asarray(toks[:, :n]), cfg, ctx))[:, :256]
            for n in range(1, 25)], axis=1)
    got = np.asarray(ref.forward(cell.config, key, toks))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
