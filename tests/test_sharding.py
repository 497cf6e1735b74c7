"""Multi-device correctness, run in a subprocess with 8 fake CPU devices so
the rest of the suite keeps seeing 1 device.

Checks that sharded execution is NUMERICALLY IDENTICAL to single-device:
train step on a 2x4 (data, model) mesh (incl. shard_map MoE) and the
sharded paged-attention decode inner.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    import sys
    sys.path.insert(0, "SRC")

    from repro import optim
    from repro.configs import ARCHS, reduced, replace
    from repro.configs.base import MoEConfig
    from repro.models import transformer as T
    from repro.train import TrainConfig, make_train_step, make_shardings

    assert jax.device_count() == 8

    # -- sharded vs single-device train step (MoE arch, exercises EP) -------
    cfg = reduced(ARCHS["deepseek-moe-16b"])
    cfg = replace(cfg, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16)
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    B, S = 4, 32
    toks = jax.random.randint(key, (2, B // 2, S), 0, cfg.vocab)
    labels = jax.random.randint(jax.random.PRNGKey(1), (2, B // 2, S), 0,
                                cfg.vocab)
    tcfg = TrainConfig(microbatches=2, compute_dtype=jnp.float32, zero1=True,
                       adamw=optim.AdamWConfig(lr=1e-3))

    # single device
    ctx1 = T.ParallelCtx(remat=False, q_block=16, kv_block=16, loss_chunk=16,
                         compute_dtype=jnp.float32)
    step1 = make_train_step(cfg, ctx1, tcfg)
    opt = optim.init(params)
    p1, o1, m1 = jax.jit(step1)(params, opt, toks, labels)

    # 2x4 mesh
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ctx2 = T.ParallelCtx(mesh=mesh, dp_axes=("data",), remat=False,
                         q_block=16, kv_block=16, loss_chunk=16,
                         compute_dtype=jnp.float32)
    step2 = make_train_step(cfg, ctx2, tcfg)
    pshape = jax.eval_shape(lambda: params)
    ins, outs = make_shardings(cfg, ctx2, tcfg, pshape)
    with mesh:
        p2, o2, m2 = jax.jit(step2, in_shardings=ins,
                             out_shardings=outs)(params, opt, toks, labels)

    loss1, loss2 = float(m1["loss"]), float(m2["loss"])
    assert abs(loss1 - loss2) < 1e-3, (loss1, loss2)
    d = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), p1, p2)
    maxdiff = max(jax.tree.leaves(d))
    assert maxdiff < 1e-3, maxdiff
    print("TRAIN_OK", loss1, loss2, maxdiff)

    # -- sharded paged decode inner vs local reference -----------------------
    from repro.configs import get_shape
    from repro.launch.serve_step import (_paged_attn_sharded, DecodePlan)
    from repro.models.attention import decode_partial, combine_partials

    plan = DecodePlan(batch_axes=("data",), kv_axes=("model",), page=4)
    Bq, Hq, Hkv, D, page, P_loc, slots = 4, 4, 2, 16, 4, 3, 8
    kvr, dp = 4, 2
    rng = np.random.default_rng(0)
    pool_k = jnp.asarray(rng.normal(size=(dp, kvr, slots, page, Hkv, D)),
                         jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(dp, kvr, slots, page, Hkv, D)),
                         jnp.float32)
    q = jnp.asarray(rng.normal(size=(Bq, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(Bq, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(Bq, Hkv, D)), jnp.float32)
    lengths = jnp.asarray([37, 30, 21, 14], jnp.int32)
    # block tables: page j of seq b lives on rank j%kvr, slot = deterministic
    bt = np.full((dp, kvr, Bq // dp, P_loc), -1, np.int32)
    app_rank = np.zeros(Bq, np.int32)
    app_slot = np.zeros(Bq, np.int32)
    app_off = np.zeros(Bq, np.int32)
    for b in range(Bq):
        n_pages = int(lengths[b]) // page + 1
        for pg in range(n_pages):
            r, j = pg % kvr, pg // kvr
            bt[b // (Bq // dp), r, b % (Bq // dp), j] = (b + pg) % slots
        cur = int(lengths[b])
        pgc = cur // page
        app_rank[b] = pgc % kvr
        app_slot[b] = (b + pgc) % slots
        app_off[b] = cur % page

    with mesh:
        cache = {"pool_k": pool_k, "pool_v": pool_v}
        upd, out = jax.jit(lambda c, *a: _paged_attn_sharded(
            c, *a, mesh=mesh, plan=plan, page=page, out_dtype=jnp.float32))(
            cache, jnp.asarray(bt), q, k, v,
            jnp.asarray(app_slot), jnp.asarray(app_off),
            jnp.asarray(app_rank), lengths)

    # reference: emulate append + gather per sequence
    pool_k_ref = np.array(pool_k)
    pool_v_ref = np.array(pool_v)
    for b in range(Bq):
        di = b // (Bq // dp)
        pool_k_ref[di, app_rank[b], app_slot[b], app_off[b]] = k[b]
        pool_v_ref[di, app_rank[b], app_slot[b], app_off[b]] = v[b]
    outs_ref = []
    for b in range(Bq):
        di, bl = b // (Bq // dp), b % (Bq // dp)
        keys, vals, valid = [], [], []
        n_pages = int(lengths[b]) // page + 1
        for pg in range(n_pages):
            r, j = pg % kvr, pg // kvr
            s = bt[di, r, bl, j]
            keys.append(pool_k_ref[di, r, s])
            vals.append(pool_v_ref[di, r, s])
            base = pg * page
            valid.append((np.arange(page) + base) <= int(lengths[b]))
        keys = jnp.asarray(np.concatenate(keys))[None]
        vals = jnp.asarray(np.concatenate(vals))[None]
        vmask = jnp.asarray(np.concatenate(valid))[None]
        m, l, a = decode_partial(q[b:b+1], keys, vals, vmask)
        outs_ref.append(combine_partials((m[None], l[None], a[None]),
                                         jnp.float32)[0])
    ref = jnp.stack(outs_ref).reshape(Bq, Hq, D)
    err = float(jnp.abs(out.reshape(Bq, Hq, D) - ref).max())
    assert err < 1e-4, err
    print("DECODE_OK", err)

    # int8 quantized pool: same attention within quantization tolerance
    plan8 = DecodePlan(batch_axes=("data",), kv_axes=("model",), page=4,
                       kv_dtype="int8")
    from repro.launch.serve_step import _quantize_token
    pk_q = np.zeros((dp, kvr, slots, page, Hkv, D), np.int8)
    sk_q = np.zeros((dp, kvr, slots, page, Hkv), np.float32)
    pv_q = np.zeros_like(pk_q)
    sv_q = np.zeros_like(sk_q)
    for di in range(dp):
        for r in range(kvr):
            for s_ in range(slots):
                kq, ks = _quantize_token(pool_k[di, r, s_])
                vq, vs = _quantize_token(pool_v[di, r, s_])
                pk_q[di, r, s_] = np.asarray(kq)
                sk_q[di, r, s_] = np.asarray(ks)
                pv_q[di, r, s_] = np.asarray(vq)
                sv_q[di, r, s_] = np.asarray(vs)
    with mesh:
        cache8 = {"pool_k": jnp.asarray(pk_q), "pool_v": jnp.asarray(pv_q),
                  "scale_k": jnp.asarray(sk_q), "scale_v": jnp.asarray(sv_q)}
        upd8, out8 = jax.jit(lambda c, *a: _paged_attn_sharded(
            c, *a, mesh=mesh, plan=plan8, page=page,
            out_dtype=jnp.float32))(
            cache8, jnp.asarray(bt), q, k, v,
            jnp.asarray(app_slot), jnp.asarray(app_off),
            jnp.asarray(app_rank), lengths)
    err8 = float(jnp.abs(out8.reshape(Bq, Hq, D) - ref).max())
    assert err8 < 0.08, err8
    print("DECODE_INT8_OK", err8)
""").replace("SRC", os.path.join(os.path.dirname(__file__), "..", "src"))


FIT_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "SRC")
    import jax, jax.numpy as jnp

    from repro import optim
    from repro.configs import get_arch, reduced
    from repro.data import DataConfig, TrainDataset
    from repro.launch import train as launch_train
    from repro.launch.mesh import make_local_mesh
    from repro.models import transformer as T
    from repro.train import TrainConfig, fit

    assert jax.device_count() == 4
    cfg = reduced(get_arch("phi3-mini-3.8b"))
    tcfg = TrainConfig(microbatches=2, compute_dtype=jnp.float32,
                       adamw=optim.AdamWConfig(lr=1e-3))
    blocks = dict(remat=False, q_block=16, kv_block=16, loss_chunk=16,
                  compute_dtype=jnp.float32)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    data = lambda: TrainDataset(DataConfig(vocab=cfg.vocab, seq_len=32,
                                           global_batch=8))

    ctx4 = T.ParallelCtx(mesh=make_local_mesh(2, 2), **blocks)
    p4, o4, h4 = fit(params, cfg, ctx4, tcfg, data(), n_steps=3, log_every=1)
    p1, o1, h1 = fit(params, cfg, T.ParallelCtx(**blocks), tcfg, data(),
                     n_steps=3, log_every=1)
    # the state lives on the mesh, and the embedding and its moments are
    # split over it, not whole on one device
    for a in jax.tree.leaves((p4, o4)):
        assert len(a.sharding.device_set) == 4, a.sharding
    for a in (p4["embed"], o4.mu["embed"], o4.nu["embed"]):
        assert a.addressable_shards[0].data.size < a.size, a.sharding
    for a, b in zip(h1, h4):
        assert abs(a["loss"] - b["loss"]) < 1e-4, (h1, h4)
    d = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()), p1, p4)))
    assert d < 1e-3, d
    print("FIT_OK", [h["loss"] for h in h4], d)

    # the launcher's own path: a (2, 2) mesh and params built sharded
    sys.argv = ["train", "--arch", "phi3-mini-3.8b", "--local", "--steps",
                "2", "--seq-len", "32", "--ckpt-dir", "CKPT"]
    assert launch_train.main() == 0
    print("LAUNCH_OK")
""").replace("SRC", os.path.join(os.path.dirname(__file__), "..", "src"))


def test_fit_on_four_devices_matches_one_device(tmp_path):
    """``fit`` over a (data 2, model 2) mesh of virtual CPU devices keeps
    params and optimizer state sharded and agrees with one device; the
    training launcher runs on that mesh."""
    script = tmp_path / "fit_check.py"
    script.write_text(FIT_SCRIPT.replace("CKPT", str(tmp_path / "ckpt")))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, env=env, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "FIT_OK" in res.stdout
    assert "LAUNCH_OK" in res.stdout


@pytest.mark.slow
def test_sharded_execution_matches_single_device(tmp_path):
    script = tmp_path / "sharded_check.py"
    script.write_text(SCRIPT)
    res = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "TRAIN_OK" in res.stdout
    assert "DECODE_OK" in res.stdout
    assert "DECODE_INT8_OK" in res.stdout
