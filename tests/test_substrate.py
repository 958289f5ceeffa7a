"""Substrate tests: optimizer, compression, data pipeline, checkpointing,
fault-tolerant runtime, sharding rules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import hypothesis_or_skip

given, settings, st = hypothesis_or_skip()

from repro.checkpoint import ckpt as ckpt_lib
from repro.data.pipeline import StreamConfig, TokenStream
from repro.optim import adamw, compression
from repro.optim.adamw import AdamWConfig
from repro.runtime import fault


# ------------------------------------------------------------------- optimizer
def test_adamw_decreases_quadratic():
    cfg = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=100,
                      weight_decay=0.0)
    params = {"w": jnp.array([3.0, -2.0])}
    state = adamw.init(cfg, params)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}  # d/dw ||w||^2
        params, state, m = adamw.update(cfg, grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_adamw_clipping_and_schedule():
    cfg = AdamWConfig(lr=1.0, clip_norm=1.0, warmup_steps=10, total_steps=100)
    params = {"w": jnp.zeros(4)}
    state = adamw.init(cfg, params)
    big = {"w": jnp.full(4, 1e6)}
    params, state, m = adamw.update(cfg, big, state, params)
    assert float(m["grad_norm"]) > 1e5
    assert float(m["lr"]) == pytest.approx(0.1, rel=1e-3)  # warmup step 1/10
    assert np.isfinite(np.asarray(params["w"])).all()


def test_adamw_bf16_moments():
    cfg = AdamWConfig(moment_dtype="bfloat16")
    params = {"w": jnp.ones((8, 8))}
    state = adamw.init(cfg, params)
    assert state.m["w"].dtype == jnp.bfloat16
    params, state, _ = adamw.update(cfg, {"w": jnp.ones((8, 8))}, state, params)
    assert state.v["w"].dtype == jnp.bfloat16


# ----------------------------------------------------------------- compression
@settings(max_examples=20, deadline=None)
@given(st.integers(0, 1000))
def test_quantize_roundtrip_error_bound(seed):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(300) * 10 ** rng.uniform(-3, 3))
    q, s, n = compression.quantize(x)
    y = compression.dequantize(q, s, n, x.shape)
    err = np.abs(np.asarray(x) - np.asarray(y))
    per_block_max = np.abs(np.asarray(x)).max()
    assert err.max() <= per_block_max / 127.0 + 1e-6


def test_error_feedback_accumulates():
    ef = compression.ef_init({"g": jnp.zeros(4)})
    g = {"g": jnp.array([1e-9, 1.0, -1.0, 0.5])}
    sent, ef = compression.ef_compress(ef, g)
    # residual carries the quantization error; next round re-injects it
    total_sent = np.asarray(sent["g"]) + np.asarray(ef.residual["g"])
    np.testing.assert_allclose(total_sent, np.asarray(g["g"]), rtol=1e-6)


def test_compressed_psum_matches_fp32():
    import jax
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("data",))
    x = jnp.asarray(np.random.RandomState(0).randn(1, 256), jnp.float32)

    def f(xs):
        return compression.compressed_psum(xs[0], "data")[None]

    y = jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                      out_specs=P("data"))(x)
    np.testing.assert_allclose(np.asarray(y)[0], np.asarray(x)[0], atol=0.1,
                               rtol=0.02)


# ------------------------------------------------------------------------ data
def test_stream_deterministic_and_step_indexed():
    cfg = StreamConfig(vocab=100, seq_len=16, global_batch=4, seed=7)
    s1, s2 = TokenStream(cfg), TokenStream(cfg)
    b5a, b5b = s1.batch(5), s2.batch(5)
    np.testing.assert_array_equal(b5a["tokens"], b5b["tokens"])
    assert not np.array_equal(s1.batch(5)["tokens"], s1.batch(6)["tokens"])


# ------------------------------------------------------------------ checkpoint
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3), "b": {"c": jnp.ones(4)}}
    ckpt_lib.save(tree, 3, str(tmp_path))
    assert ckpt_lib.latest_step(str(tmp_path)) == 3
    out = ckpt_lib.restore(tree, 3, str(tmp_path))
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_async_checkpointer(tmp_path):
    tree = {"w": jnp.ones((16, 16))}
    saver = ckpt_lib.AsyncCheckpointer(str(tmp_path))
    saver.save(tree, 1)
    saver.save(tree, 2)
    paths = saver.wait()
    assert len(paths) == 2
    assert ckpt_lib.latest_step(str(tmp_path)) == 2


def test_restore_with_resharding(tmp_path):
    """Elastic restore: same data re-placed under a new sharding/mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    tree = {"w": jnp.arange(8.0)}
    ckpt_lib.save(tree, 0, str(tmp_path))
    mesh = jax.make_mesh((1,), ("data",))
    sh = {"w": NamedSharding(mesh, P(None))}
    out = ckpt_lib.restore(tree, 0, str(tmp_path), shardings=sh)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.arange(8.0))


# ----------------------------------------------------------------- fault loop
def test_recovery_resumes_from_checkpoint(tmp_path):
    calls = []

    def step_fn(state, batch, step):
        calls.append(step)
        return {"x": state["x"] + 1}, {}

    injector = fault.FailureInjector([7])
    cfg = fault.TrainLoopConfig(total_steps=12, ckpt_every=3,
                                ckpt_dir=str(tmp_path))
    state, hist = fault.run_with_recovery(
        cfg, init_state={"x": jnp.zeros(())}, step_fn=step_fn,
        make_batch=lambda s: None, injector=injector)
    assert hist["recoveries"] == 1
    # restored at step 6+1: steps 7..12 re-run; final x == completed steps
    assert float(state["x"]) == len(set(calls))
    assert sorted(set(calls)) == list(range(12))


def test_watchdog_flags_stragglers():
    wd = fault.StepWatchdog(factor=3.0)
    for _ in range(6):
        wd.observe(0, 0.1)
    assert wd.observe(6, 1.0)
    assert not wd.observe(7, 0.12)


# --------------------------------------------------------------------- sharding
def test_param_specs_divisibility():
    from jax.sharding import PartitionSpec as P

    from repro import configs
    from repro.models import registry
    from repro.parallel import sharding


    # qwen2: 60 experts not divisible by model axis in production; verify the
    # rule logic directly against a fake 16-way mesh via _maybe
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    fm = FakeMesh()
    assert sharding._maybe(fm, 64, "model") == "model"   # olmoe experts
    assert sharding._maybe(fm, 60, "model") is None      # qwen2 experts
    assert sharding._maybe(fm, 49408, "model") == "model"  # padded vocab

    spec = sharding._param_spec(fm, "we1", (24, 60, 2048, 1408), False)
    assert spec == P(None, None, None, "model")  # falls to expert-FF dim
    spec = sharding._param_spec(fm, "we1", (16, 64, 2048, 1024), False)
    assert spec == P(None, "model", None, None)  # true EP
    # 4D attention weights: heads shard when divisible, else REPLICATE
    # (never head_dim — contraction sharding regression, EXPERIMENTS SSPerf)
    spec = sharding._param_spec(fm, "wq", (40, 4096, 32, 128), False)
    assert spec == P(None, None, "model", None)
    spec = sharding._param_spec(fm, "wq", (12, 768, 12, 64), False)
    assert spec == P(None, None, None, None)


def test_batch_specs_b1_replicates():
    from repro.parallel import sharding

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    assert sharding._dp_if_div(FakeMesh(), 1) is None
    assert sharding._dp_if_div(FakeMesh(), 128) == ("data",)
