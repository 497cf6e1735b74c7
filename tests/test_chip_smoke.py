"""chip_smoke.py's logic on the CPU: the serve check at a reduced phi3, and
its refusal to run without a TPU."""
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402
from repro.configs import get_arch, reduced  # noqa: E402
from repro.models import decode as D  # noqa: E402
from repro.models import transformer as T  # noqa: E402

GEO = chip_smoke.Geometry(requests=6, prompt=16, new=12, batch=3, page=4,
                          roomy_slots=48)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_serve_check_reduced_phi3(dtype):
    cfg = reduced(get_arch(chip_smoke.ARCH))
    ctx = T.ParallelCtx(remat=False, q_block=16, kv_block=16,
                        compute_dtype=dtype)
    params = T.init_params(jax.random.PRNGKey(0), cfg, dtype)
    prompts = chip_smoke.make_prompts(GEO, cfg.vocab)
    # as serve_phases: the reference on the stacked weights, then one split
    # that both engines serve from
    ref = chip_smoke.reference_logits(params, cfg, prompts[0])
    D.take_layers(params)
    eng, roomy, pressured = chip_smoke.serve_check(params, cfg, ctx, GEO,
                                                   prompts)
    assert all(a is b for a, b in zip(jax.tree.leaves(eng.params),
                                      jax.tree.leaves(params)))
    assert pressured == roomy
    assert all(len(t) == GEO.new for t in pressured)
    s = eng.stats
    assert eng.pool.size == GEO.pressured_slots < GEO.roomy_slots
    assert s.pauses > 0 and s.demoted_pages > 0 and s.flushed_pages > 0
    assert s.repointed_pages + s.streamed_pages > 0
    assert eng.caches["layers"][0]["pool"].k.dtype == dtype
    rel = chip_smoke.reference_check(eng, ref, cfg, prompts[0])
    assert rel < (1e-5 if dtype == jnp.float32 else
                  chip_smoke.LOGIT_REL_L2_TOL)


def test_refuses_to_run_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, env=env,
                         cwd=tmp_path, timeout=300)
    assert res.returncode != 0
    assert "platform 'cpu'" in res.stderr
    assert '"ok"' not in res.stdout


def test_train_check_on_four_devices():
    """The ``--chips 4`` phase's logic at a reduced phi3, on four virtual CPU
    devices: the (data 2, model 2) step agrees with the one-device step."""
    code = ("import chip_smoke\n"
            "from repro.configs import get_arch, reduced\n"
            "chip_smoke.train_check(reduced(get_arch(chip_smoke.ARCH)),\n"
            "                       chip_smoke.CompileLog(), seq=32)\n"
            "print('TRAIN_OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "TRAIN_OK" in res.stdout
    assert "max_param_diff=" in res.stdout


def test_compile_cache_location(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set; without
    it the cache is the checkout's git-ignored ``.jax_cache``."""
    from repro.launch.compile_cache import enable_compile_cache
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "elsewhere/cache")
    assert enable_compile_cache() == "elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == was
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert path == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
