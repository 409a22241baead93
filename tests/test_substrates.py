"""Tests: data pipeline, checkpointing, fault tolerance, optimizer,
gradient compression."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.checkpoint import (CheckpointConfig, CheckpointManager,
                              load_checkpoint, save_checkpoint)
from repro.data import DataConfig, ShardedLoader, make_dataset
from repro.ft import (ClusterState, HeartbeatMonitor, StragglerTracker,
                      plan_remesh)
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         compress_int8, decompress_int8)
from repro.optim.compression import ErrorFeedback


# ------------------------------------------------------------- data --------

def test_synthetic_data_deterministic_and_rank_disjoint():
    base = dict(vocab=100, seq_len=8, global_batch=8, seed=7, dp_size=2)
    d0 = make_dataset(DataConfig(dp_rank=0, **base))
    d1 = make_dataset(DataConfig(dp_rank=1, **base))
    b0a, b0b = d0.batch_at(3), d0.batch_at(3)
    np.testing.assert_array_equal(b0a["tokens"], b0b["tokens"])  # determinism
    b1 = d1.batch_at(3)
    assert not np.array_equal(b0a["tokens"], b1["tokens"])       # disjoint
    assert b0a["tokens"].shape == (4, 8)                          # local B
    np.testing.assert_array_equal(d0.batch_at(4)["tokens"][:, 1:],
                                  d0.batch_at(4)["labels"][:, :-1])


def test_memmap_dataset(tmp_path):
    path = tmp_path / "tokens.bin"
    arr = np.arange(10000, dtype=np.int32)
    arr.tofile(path)
    cfg = DataConfig(vocab=1 << 20, seq_len=16, global_batch=4,
                     source="memmap", path=str(path))
    ds = make_dataset(cfg)
    b = ds.batch_at(0)
    assert b["tokens"].shape == (4, 16)
    np.testing.assert_array_equal(b["labels"], b["tokens"] + 1)


def test_loader_resume_exactly():
    cfg = DataConfig(vocab=50, seq_len=4, global_batch=2)
    ds = make_dataset(cfg)
    loader = ShardedLoader(ds, prefetch=1)
    seen = [next(loader) for _ in range(3)]
    state = loader.state_dict()
    nxt = next(loader)
    loader.close()
    resumed = ShardedLoader.resume(ds, state, prefetch=1)
    nxt2 = next(resumed)
    resumed.close()
    np.testing.assert_array_equal(nxt["tokens"], nxt2["tokens"])


# -------------------------------------------------------- checkpoint -------

def _tree():
    return {"w": jnp.arange(12.0).reshape(3, 4),
            "nested": {"b": jnp.ones((5,), jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(tmp_path, 7, t, extra={"data_step": 42})
    restored, extra = load_checkpoint(tmp_path, t)
    assert extra == {"data_step": 42}
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), t, restored)


def test_checkpoint_corruption_detected(tmp_path):
    t = _tree()
    d = save_checkpoint(tmp_path, 1, t)
    # flip bytes in a leaf file
    f = d / "arr_000000.npy"
    raw = bytearray(f.read_bytes())
    raw[-1] ^= 0xFF
    f.write_bytes(bytes(raw))
    with pytest.raises(IOError, match="corruption"):
        load_checkpoint(tmp_path, t)


def test_checkpoint_atomicity_ignores_tmp(tmp_path):
    t = _tree()
    save_checkpoint(tmp_path, 3, t)
    # simulate a crashed half-write at a later step
    crashed = tmp_path / "step_000000009.tmp"
    crashed.mkdir()
    (crashed / "arr_000000.npy").write_bytes(b"garbage")
    restored, _ = load_checkpoint(tmp_path, t)   # picks committed step 3
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(t["w"]))


def test_checkpoint_manager_async_and_retention(tmp_path):
    mgr = CheckpointManager(CheckpointConfig(root=str(tmp_path), keep=2))
    t = _tree()
    for step in (1, 2, 3, 4):
        mgr.save(step, jax.tree.map(lambda a: a + step, t),
                 extra={"s": step})
    mgr.wait()
    kept = sorted(p.name for p in tmp_path.iterdir())
    assert kept == ["step_000000003", "step_000000004"]
    restored, extra = mgr.restore_latest(t)
    assert extra["s"] == 4
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(t["w"]) + 4)


def test_checkpoint_manager_concurrent_writers_no_deadlock(tmp_path):
    """Regression: concurrent async saves must use distinct MCS queue
    nodes (same-unit self-enqueue used to deadlock)."""
    mgr = CheckpointManager(CheckpointConfig(root=str(tmp_path), keep=12))
    t = _tree()
    for step in range(10):            # > MAX_WRITERS concurrent saves
        mgr.save(step, t, extra={"s": step})
    mgr.wait()                        # must not hang
    steps = sorted(int(p.name.split("_")[1]) for p in tmp_path.iterdir())
    assert steps == list(range(10))


def test_checkpoint_resume_determinism(tmp_path):
    """Full restart loop: state+data cursor restored => identical run."""
    cfg = DataConfig(vocab=64, seq_len=4, global_batch=2)
    ds = make_dataset(cfg)

    def run(n_steps, start_state=None, start_cursor=0):
        params = (start_state if start_state is not None
                  else jnp.zeros((64,)))
        loader = ShardedLoader(ds, start_step=start_cursor, prefetch=1)
        for _ in range(n_steps):
            b = next(loader)
            params = params + np.bincount(
                b["tokens"].ravel(), minlength=64)
        cursor = loader.state_dict()["step"]
        loader.close()
        return params, cursor

    full, _ = run(6)
    half, cur = run(3)
    save_checkpoint(tmp_path, 3, {"p": half}, extra={"cursor": cur})
    restored, extra = load_checkpoint(tmp_path, {"p": half})
    resumed, _ = run(3, start_state=restored["p"],
                     start_cursor=extra["cursor"])
    np.testing.assert_array_equal(np.asarray(full), np.asarray(resumed))


# ---------------------------------------------------------------- ft -------

def test_heartbeat_declares_dead():
    clock = {"t": 0.0}
    cluster = ClusterState(n_hosts=4, devices_per_host=8)
    mon = HeartbeatMonitor(cluster, interval_s=1.0, miss_threshold=3,
                           clock=lambda: clock["t"])
    clock["t"] = 2.0
    for h in (0, 1, 2):
        mon.beat(h)
    clock["t"] = 4.0
    assert mon.sweep() == [3]
    assert cluster.alive_hosts == [0, 1, 2]


def test_plan_remesh_shrinks_data_axis():
    cluster = ClusterState(n_hosts=64, devices_per_host=8)   # 512 devices
    for h in (5, 6, 7, 8):
        cluster.alive[h] = False                              # lose 32 dev
    plan = plan_remesh(cluster, model_parallel=16, pods=2)
    assert plan.mesh_axes == ("pod", "data", "model")
    pods, data, model = plan.mesh_shape
    assert model == 16 and pods == 2
    assert pods * data * model <= 60 * 8
    assert plan.dropped_devices == 60 * 8 - pods * data * model


def test_plan_remesh_raises_when_model_axis_unsatisfiable():
    cluster = ClusterState(n_hosts=1, devices_per_host=8)
    with pytest.raises(RuntimeError):
        plan_remesh(cluster, model_parallel=16)


def test_straggler_tracker_and_rebalance():
    tr = StragglerTracker(n_hosts=4, ratio=1.5)
    for _ in range(10):
        for h, t in enumerate([1.0, 1.0, 1.0, 2.5]):
            tr.record(h, t)
    assert tr.stragglers() == [3]
    plan = tr.rebalance_plan({0: 4, 1: 4, 2: 4, 3: 4})
    assert plan[3] == 3 and sum(plan.values()) == 16


# ------------------------------------------------------ optimizer ----------

def test_adamw_decreases_quadratic_loss():
    cfg = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=100,
                      weight_decay=0.0)
    params = {"w": jnp.array([5.0, -3.0])}
    opt = adamw_init(params)

    def loss(p):
        return jnp.sum(jnp.square(p["w"]))

    l0 = float(loss(params))
    for _ in range(50):
        g = jax.grad(loss)(params)
        params, opt, _ = adamw_update(cfg, g, opt, params)
    assert float(loss(params)) < l0 * 0.05
    assert int(opt["step"]) == 50


def test_adamw_grad_clip_metric():
    cfg = AdamWConfig(grad_clip=1.0, warmup_steps=0)
    params = {"w": jnp.ones((4,))}
    opt = adamw_init(params)
    g = {"w": jnp.full((4,), 100.0)}
    _, _, m = adamw_update(cfg, g, opt, params)
    assert float(m["grad_norm"]) == pytest.approx(200.0)


# ---------------------------------------------------- compression ----------

@given(st.integers(0, 2 ** 31 - 1), st.floats(0.1, 100.0))
@settings(max_examples=20, deadline=None)
def test_int8_roundtrip_error_bound(seed, scale):
    rng = np.random.RandomState(seed % (2 ** 31))
    x = jnp.asarray(rng.randn(64) * scale, jnp.float32)
    q, s = compress_int8(x)
    y = decompress_int8(q, s)
    assert q.dtype == jnp.int8
    # quantization error bounded by half a step
    step = float(s)
    assert float(jnp.max(jnp.abs(y - x))) <= step * 0.5 + 1e-6


def test_error_feedback_reduces_bias():
    rng = np.random.RandomState(0)
    g_stream = [jnp.asarray(rng.randn(128) * 0.01, jnp.float32)
                for _ in range(50)]
    # without EF: accumulate quantized; with EF: residual carried
    acc_plain = np.zeros(128)
    acc_ef = np.zeros(128)
    residual = jnp.zeros(128)
    for g in g_stream:
        q, s = compress_int8(g)
        acc_plain += np.asarray(decompress_int8(q, s))
        corrected = g + residual
        q2, s2 = compress_int8(corrected)
        d2 = decompress_int8(q2, s2)
        residual = corrected - d2
        acc_ef += np.asarray(d2)
    truth = np.sum([np.asarray(g) for g in g_stream], axis=0)
    err_plain = np.linalg.norm(acc_plain - truth)
    err_ef = np.linalg.norm(acc_ef - truth)
    assert err_ef < err_plain * 0.9
