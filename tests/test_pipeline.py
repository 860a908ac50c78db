"""Async step pipeline: lazy fetch handles, bounded in-flight window,
device-side prefetch, persistent compile cache, and the synchronous
degenerate configuration (depth=1 + cache-off)."""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer, static
from paddle_tpu import observability as obs
from paddle_tpu.core.pipeline import (FetchHandle, InFlightWindow,
                                      pipeline_depth)
from paddle_tpu.io import DataLoader, Dataset, DeviceFeeder

pytestmark = pytest.mark.perf


@pytest.fixture(autouse=True)
def _static_guard():
    yield
    paddle.disable_static()
    os.environ.pop("PADDLE_TPU_PIPELINE_DEPTH", None)


@pytest.fixture
def _obs():
    obs.enable(True)
    obs.get_timeline().clear()
    yield obs
    obs.get_timeline().clear()
    obs.disable()


def _linreg_program(seed=0):
    """x @ w + b MSE training program, deterministic under the seed."""
    paddle.seed(seed)
    main = static.Program()
    startup = static.Program()
    with static.program_guard(main, startup):
        x = static.data("x", [8, 4], "float32")
        y = static.data("y", [8, 1], "float32")
        lin = nn.Linear(4, 1)
        loss = paddle.nn.functional.mse_loss(lin(x), y)
        opt = optimizer.SGD(learning_rate=0.1,
                            parameters=lin.parameters())
        opt.minimize(loss)
    return main, loss


def _feeds(n=4, seed=0):
    rng = np.random.RandomState(seed)
    return [{"x": rng.rand(8, 4).astype(np.float32),
             "y": rng.rand(8, 1).astype(np.float32)} for _ in range(n)]


# -- depth knob ----------------------------------------------------------
def test_pipeline_depth_env():
    assert pipeline_depth() == 2  # default
    os.environ["PADDLE_TPU_PIPELINE_DEPTH"] = "5"
    assert pipeline_depth() == 5
    os.environ["PADDLE_TPU_PIPELINE_DEPTH"] = "0"
    assert pipeline_depth() == 1  # clamped
    os.environ["PADDLE_TPU_PIPELINE_DEPTH"] = "junk"
    assert pipeline_depth() == 2  # default on garbage


# -- FetchHandle ---------------------------------------------------------
def test_fetch_handle_reads():
    paddle.enable_static()
    main, loss = _linreg_program()
    exe = static.Executor()
    (h,) = exe.run(main, feed=_feeds(1)[0], fetch_list=[loss],
                   return_numpy=False)
    assert isinstance(h, FetchHandle)
    assert h.shape == () and h.dtype == np.float32
    v = h.numpy()
    assert isinstance(v, np.ndarray) and np.isfinite(v)
    assert float(h) == float(v) and h.item() == v.item()
    assert np.asarray(h) is v  # cached host copy
    assert "ready" in repr(h)
    t = h.tensor()
    assert float(t) == float(v)


def test_fetch_handle_matches_numpy_path():
    paddle.enable_static()
    main, loss = _linreg_program(seed=3)
    exe = static.Executor()
    fd = _feeds(1, seed=3)[0]
    (sync,) = exe.run(main, feed=fd, fetch_list=[loss])

    main2, loss2 = _linreg_program(seed=3)
    (h,) = static.Executor().run(main2, feed=fd, fetch_list=[loss2],
                                 return_numpy=False)
    assert np.array_equal(sync, h.numpy())


# -- in-flight window ----------------------------------------------------
def test_window_blocks_past_depth():
    import jax.numpy as jnp
    w = InFlightWindow(depth=2)
    w.admit((jnp.ones(4),), label="a")
    assert len(w) == 1
    w.admit((jnp.ones(4),), label="b")
    assert len(w) == 1  # oldest was blocked out
    w.drain()
    assert len(w) == 0


def test_window_depth1_is_synchronous():
    import jax.numpy as jnp
    w = InFlightWindow(depth=1)
    w.admit((jnp.ones(4),), label="a")
    assert len(w) == 0  # blocked before admit returned


def test_depth1_cache_off_bitwise_parity():
    paddle.enable_static()
    feeds = _feeds(4, seed=1)
    main, loss = _linreg_program(seed=1)
    exe = static.Executor()
    base = [exe.run(main, feed=fd, fetch_list=[loss])[0] for fd in feeds]

    os.environ["PADDLE_TPU_PIPELINE_DEPTH"] = "1"
    main2, loss2 = _linreg_program(seed=1)
    exe2 = static.Executor()
    for i, fd in enumerate(feeds):
        (h,) = exe2.run(main2, feed=fd, fetch_list=[loss2],
                        return_numpy=False, use_program_cache=False)
        assert h.is_ready()
        assert np.array_equal(base[i], h.numpy()), i


# -- executor program cache ----------------------------------------------
def test_use_program_cache_false_recompiles(_obs):
    paddle.enable_static()
    main, loss = _linreg_program()
    exe = static.Executor()
    fd = _feeds(1)[0]
    exe.run(main, feed=fd, fetch_list=[loss])
    exe.run(main, feed=fd, fetch_list=[loss])  # cached: no new compile
    n_cached = obs.phase_breakdown()["compile_count"]
    exe.run(main, feed=fd, fetch_list=[loss], use_program_cache=False)
    assert obs.phase_breakdown()["compile_count"] == n_cached + 1


def test_shared_cache_across_executor_instances(_obs):
    paddle.enable_static()
    main, loss = _linreg_program()
    fd = _feeds(1)[0]
    static.Executor().run(main, feed=fd, fetch_list=[loss])
    n = obs.phase_breakdown()["compile_count"]
    # a FRESH Executor reuses the shared fingerprint-keyed entry
    (res,) = static.Executor().run(main, feed=fd, fetch_list=[loss])
    assert obs.phase_breakdown()["compile_count"] == n
    assert np.isfinite(res)


def test_clear_shared_cache(_obs):
    paddle.enable_static()
    main, loss = _linreg_program()
    fd = _feeds(1)[0]
    static.Executor().run(main, feed=fd, fetch_list=[loss])
    n = obs.phase_breakdown()["compile_count"]
    static.Executor.clear_shared_cache()
    static.Executor().run(main, feed=fd, fetch_list=[loss])
    assert obs.phase_breakdown()["compile_count"] == n + 1


# -- DeviceFeeder --------------------------------------------------------
def test_device_feeder_basic():
    import jax
    feeds = _feeds(3)
    with DeviceFeeder(feeds) as feeder:
        assert len(feeder) == 3
        got = list(feeder)
    assert len(got) == 3
    for fd, dev in zip(feeds, got):
        assert isinstance(dev["x"], jax.Array)
        np.testing.assert_array_equal(fd["x"], np.asarray(dev["x"]))


def test_device_feeder_early_exit_and_reuse():
    feeder = DeviceFeeder(_feeds(4))
    it = iter(feeder)
    next(it)  # abandon the epoch after one batch
    # a new epoch restarts cleanly from the beginning
    assert len(list(feeder)) == 4
    feeder.close()
    feeder.close()  # idempotent


def test_device_feeder_executor_parity():
    paddle.enable_static()
    feeds = _feeds(3, seed=2)
    main, loss = _linreg_program(seed=2)
    exe = static.Executor()
    base = [exe.run(main, feed=fd, fetch_list=[loss])[0] for fd in feeds]

    main2, loss2 = _linreg_program(seed=2)
    exe2 = static.Executor()
    got = []
    with DeviceFeeder(feeds) as feeder:
        for fd in feeder:
            got.append(exe2.run(main2, feed=fd, fetch_list=[loss2])[0])
    for a, b in zip(base, got):
        np.testing.assert_allclose(a, b, rtol=1e-6)


# -- persistent_workers --------------------------------------------------
class _ArangeDS(Dataset):
    def __getitem__(self, i):
        return (np.asarray([i], np.float32),)

    def __len__(self):
        return 8


def test_persistent_workers_reuse_pool():
    dl = DataLoader(_ArangeDS(), batch_size=2, num_workers=2,
                    shuffle=False, persistent_workers=True)
    try:
        e1 = [b[0].numpy().ravel().tolist() for b in dl]
        pool1 = dl._mp_pool or dl._thread_pool
        assert pool1 is not None, "persistent pool not retained"
        e2 = [b[0].numpy().ravel().tolist() for b in dl]
        assert (dl._mp_pool or dl._thread_pool) is pool1
        assert e1 == e2 == [[0.0, 1.0], [2.0, 3.0], [4.0, 5.0],
                            [6.0, 7.0]]
    finally:
        dl.shutdown()
    assert dl._mp_pool is None and dl._thread_pool is None


def test_persistent_workers_early_exit_drains():
    dl = DataLoader(_ArangeDS(), batch_size=2, num_workers=2,
                    shuffle=False, persistent_workers=True)
    try:
        it = iter(dl)
        next(it)
        del it  # abandon mid-epoch: pending work must drain
        full = [float(b[0].numpy()[0, 0]) for b in dl]
        assert full == [0.0, 2.0, 4.0, 6.0]
    finally:
        dl.shutdown()


def test_feeder_over_persistent_loader():
    dl = DataLoader(_ArangeDS(), batch_size=4, num_workers=2,
                    shuffle=False, persistent_workers=True)
    try:
        with DeviceFeeder(dl) as feeder:
            for _ in range(2):  # two epochs over live workers
                got = [np.asarray(b[0]).ravel().tolist() for b in feeder]
                assert got == [[0.0, 1.0, 2.0, 3.0],
                               [4.0, 5.0, 6.0, 7.0]]
    finally:
        dl.shutdown()


# -- memory guard integration --------------------------------------------
def test_estimate_pipeline_fields():
    from paddle_tpu.memory.estimator import MemoryEstimate
    mib = 1 << 20
    est = MemoryEstimate(argument_bytes=100 * mib, output_bytes=50 * mib,
                         temp_bytes=25 * mib, pipeline_bytes=75 * mib,
                         pipeline_depth=4)
    assert est.total_bytes == 250 * mib
    rows = dict(est.top_buffers())
    assert rows["<pipeline in-flight buffers (depth=4)>"] == 75 * mib
    d = est.to_dict()
    assert d["pipeline_depth"] == 4 and d["pipeline_gb"] > 0


def test_hbm_budget_error_names_pipeline_buffers():
    from paddle_tpu.memory.errors import HbmBudgetError
    from paddle_tpu.memory.estimator import MemoryEstimate
    est = MemoryEstimate(argument_bytes=2 << 30, output_bytes=1 << 30,
                         pipeline_bytes=1 << 30, pipeline_depth=3)
    err = HbmBudgetError("prog", est, budget=1 << 30,
                         top_buffers=est.top_buffers())
    msg = str(err)
    assert "pipeline in-flight buffers" in msg
    assert "PADDLE_TPU_PIPELINE_DEPTH=3" in msg
    assert "lower the depth to 1" in msg


def test_preflight_accounts_for_depth(monkeypatch):
    from paddle_tpu.memory import guard
    from paddle_tpu.memory.errors import HbmBudgetError
    from paddle_tpu.memory.estimator import MemoryEstimate

    def fake_analyze(compiled, program=None, named_buffers=None):
        return MemoryEstimate(program=program or "p",
                              argument_bytes=1000, output_bytes=600,
                              temp_bytes=100)

    monkeypatch.setenv(guard.ENV_MEMORY_GUARD, "on")
    monkeypatch.setattr(guard, "analyze_compiled", fake_analyze)
    # depth 3 keeps 2 extra steps of outputs+feeds live: over budget
    with pytest.raises(HbmBudgetError) as ei:
        guard.preflight_check(None, program="p", budget=2000,
                              pipeline_depth=3, per_step_io_bytes=400)
    assert ei.value.estimate.pipeline_bytes == 2 * (600 + 400)
    assert "pipeline in-flight buffers" in str(ei.value)
    # depth 1: no pipeline charge, same program fits
    est = guard.preflight_check(None, program="p", budget=2000,
                                pipeline_depth=1, per_step_io_bytes=400)
    assert est.pipeline_bytes == 0


def test_preflight_charges_a_donated_output_once(monkeypatch):
    """A serving step's pools come back as outputs that alias the
    donated arguments: the next step in flight reads the same buffers,
    so depth charges only what aliases nothing."""
    from paddle_tpu.memory import guard
    from paddle_tpu.memory.estimator import MemoryEstimate

    def fake_analyze(compiled, program=None, named_buffers=None):
        return MemoryEstimate(program=program or "p",
                              argument_bytes=1000, output_bytes=600,
                              temp_bytes=100, alias_bytes=550)

    monkeypatch.setenv(guard.ENV_MEMORY_GUARD, "on")
    monkeypatch.setattr(guard, "analyze_compiled", fake_analyze)
    est = guard.preflight_check(None, program="p", budget=2000,
                                pipeline_depth=3, per_step_io_bytes=40)
    assert est.pipeline_bytes == 2 * (600 - 550 + 40)
    assert est.total_bytes == 1000 + 600 + 100 - 550 + 180


# -- persistent compile cache --------------------------------------------
@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_persists_to_dir(tmp_path, monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR set: the program sets no directory and
    writes where JAX was told; unset: one fixed path in the checkout."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as jcc
    from paddle_tpu.device import compile_cache as cc
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    assert cc.ensure_compile_cache() is None      # conftest: cache off
    cache = tmp_path / "xla_cache"
    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    monkeypatch.setattr(cc, "_applied", False)
    if env_dir:
        # what `JAX_COMPILATION_CACHE_DIR=... python` gives: JAX reads
        # the variable into its config at import
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
        jax.config.update("jax_compilation_cache_dir", str(cache))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(cc, "DEFAULT_CACHE_DIR", str(cache))
    jax.config.update("jax_enable_compilation_cache", True)
    jcc.reset_cache()
    try:
        assert cc.ensure_compile_cache() == str(cache)
        paddle.enable_static()
        main, loss = _linreg_program()
        static.Executor().run(main, feed=_feeds(1)[0], fetch_list=[loss],
                              use_program_cache=False)
        files = [p for p in cache.rglob("*") if p.is_file()]
        assert files, "compile did not persist to the cache dir"
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        jcc.reset_cache()
    assert cc.ensure_compile_cache() is None


# -- pipeline_stats ------------------------------------------------------
def test_pipeline_stats_synthetic():
    from paddle_tpu.observability.timeline import Event
    evs = [
        # step 0 dispatched at t=0 (enqueue takes 0.1), synced at 5..6
        Event("dispatch s0", "dispatch", 0.0, 0.1),
        Event("pipeline.wait:s0", "pipeline", 5.0, 1.0),
        # prefetch of the next batch runs at 2..3, fully in flight
        Event("h2d:prefetch", "h2d", 2.0, 1.0),
    ]
    s = obs.pipeline_stats(evs)
    assert s["overlap_ratio"] == 1.0
    assert s["measured_depth"] == 2
    assert s["dispatch_count"] == 1 and s["h2d_count"] == 1


def test_pipeline_stats_serial_trace_no_overlap():
    from paddle_tpu.observability.timeline import Event
    # h2d then dispatch with no sync events: nothing may be fabricated
    evs = [
        Event("h2d:feed", "h2d", 0.0, 1.0),
        Event("dispatch s0", "dispatch", 1.5, 0.5),
        Event("h2d:feed", "h2d", 3.0, 1.0),
        Event("dispatch s1", "dispatch", 4.5, 0.5),
    ]
    s = obs.pipeline_stats(evs)
    assert s["overlap_ratio"] == 0.0
    assert s["measured_depth"] == 1
