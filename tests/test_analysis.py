"""tpu_lint static analysis: tiling legality, recompile risk, host
sync, dtype audits, probe diagnosis, and the CLI gate over the bundled
models (ISSUE 6)."""
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import analysis, nn, optimizer
from paddle_tpu import observability as obs
from paddle_tpu.analysis import (audit_host_sync, audit_jaxpr,
                                 check_block_spec, check_pallas_call,
                                 min_tile)
from paddle_tpu.analysis.diagnostics import (CODES, Diagnostic,
                                             DiagnosticReport, get_log,
                                             record, reset_log)
from paddle_tpu.observability.timeline import Event
import paddle_tpu.nn.functional as F

pytestmark = pytest.mark.analysis


@pytest.fixture(autouse=True)
def _clean_log():
    reset_log()
    yield
    reset_log()


def codes(diags):
    return [d.code for d in diags]


# ---------------------------------------------------------------------
# Tiling legality (TPU1xx)
# ---------------------------------------------------------------------
class TestTiling:
    def test_min_tile_by_dtype(self):
        assert min_tile(jnp.float32) == (8, 128)
        assert min_tile(jnp.bfloat16) == (16, 128)
        assert min_tile(jnp.int8) == (32, 128)

    def test_illegal_f32_sublane_block(self):
        # the acceptance case: the (1,128) f32 q-block that Mosaic
        # refused on the chip in round 2 must be flagged TPU101
        diags = check_block_spec((1, 128), (1024, 128), jnp.float32,
                                 site="t", operand="q")
        assert codes(diags) == ["TPU101"]
        assert diags[0].severity == "error"
        assert "q" in diags[0].site

    def test_legal_f32_block(self):
        assert check_block_spec((8, 128), (1024, 128),
                                jnp.float32) == []

    def test_bf16_needs_16_rows(self):
        assert codes(check_block_spec(
            (8, 128), (1024, 128), jnp.bfloat16)) == ["TPU101"]
        assert check_block_spec((16, 128), (1024, 128),
                                jnp.bfloat16) == []

    def test_int8_needs_32_rows(self):
        assert codes(check_block_spec(
            (16, 128), (1024, 128), jnp.int8)) == ["TPU101"]
        assert check_block_spec((32, 128), (1024, 128), jnp.int8) == []

    def test_full_dim_block_always_legal(self):
        # block == array dim is legal even below the minimum tile
        assert check_block_spec((4, 128), (4, 128), jnp.float32) == []

    def test_ragged_grid_flagged(self):
        # 24 is a multiple of 8 but does not divide 64
        assert codes(check_block_spec(
            (24, 128), (64, 128), jnp.float32)) == ["TPU102"]

    def test_leading_dim_must_divide(self):
        assert codes(check_block_spec(
            (3, 8, 128), (4, 64, 128), jnp.float32)) == ["TPU102"]

    def test_rank1_warns(self):
        diags = check_block_spec((128,), (1024,), jnp.float32)
        assert codes(diags) == ["TPU104"]
        assert diags[0].severity == "warning"

    def test_whole_array_block_legal(self):
        assert check_block_spec(None, (7, 3), jnp.float32) == []

    def test_vmem_overflow(self):
        report = check_pallas_call(
            [("x", (2048, 2048), (8192, 2048), jnp.float32)],
            site="huge")
        assert codes(report) == ["TPU103"]
        assert report.max_severity() == "error"

    def test_report_helpers(self):
        report = check_pallas_call(
            [("q", (1, 128), (1024, 128), jnp.float32)], site="k")
        assert not report.ok()
        assert report.ok(fail_on="never")
        assert report.counts() == {"TPU101": 1}
        assert "TPU101" in report.render()


# ---------------------------------------------------------------------
# Flash attention block plans (satellite b)
# ---------------------------------------------------------------------
class TestKernelPlans:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("seq", [8, 17, 64, 128, 1024])
    def test_flash_plan_legal(self, dtype, seq):
        report = analysis.audit_flash_attention(
            batch=1, seq_q=seq, seq_k=seq, heads=2, head_dim=64,
            dtype=dtype, causal=True)
        assert list(report) == [], report.render()
        sub_min, _ = min_tile(dtype)
        assert report.plan["block_q"] % sub_min == 0

    def test_flash_interpret_runs_at_plan_shape(self):
        # the dtype-aware plan must both pass the static check and
        # produce finite output through the interpret-mode kernel
        from paddle_tpu.ops import pallas_kernels as pk
        report = analysis.audit_flash_attention(
            batch=1, seq_q=64, seq_k=64, heads=2, head_dim=64,
            dtype=jnp.bfloat16, causal=True)
        assert list(report) == []
        q = jnp.ones((1, 64, 2, 64), jnp.bfloat16) * 0.1
        out = pk.flash_attention(q, q, q, causal=True)
        assert out.shape == q.shape
        assert bool(jnp.isfinite(out.astype(jnp.float32)).all())


# ---------------------------------------------------------------------
# Probe gate diagnosis (satellite a)
# ---------------------------------------------------------------------
class TestProbeGate:
    def test_force_probe_ok_on_cpu(self):
        from paddle_tpu.ops import pallas_gate as pg
        pg.reset_probe_cache()
        try:
            res = pg.probe_kernel("layer_norm", force=True)
            assert res.ok, res.error
            rep = pg.probe_report("layer_norm")
            assert rep == {"kernel": "layer_norm", "ok": True,
                           "probed": True}
        finally:
            pg.reset_probe_cache()

    def test_unprobed_kernels_reported(self):
        from paddle_tpu.ops import pallas_gate as pg
        pg.reset_probe_cache()
        assert pg.probe_report()["flash_attention"] == {"probed": False}

    def test_probe_failure_diagnosed(self, monkeypatch):
        from paddle_tpu.ops import pallas_gate as pg

        def boom():
            raise RuntimeError("Mosaic failed to compile: bad tile")

        pg.reset_probe_cache()
        monkeypatch.setitem(pg._PROBES, "flash_attention", boom)
        try:
            with obs.enabled_scope():
                res = pg.probe_kernel("flash_attention", force=True)
            assert not res.ok
            assert res.error_type == "RuntimeError"
            assert "Mosaic" in res.error
            assert "TPU110" in codes(res.diagnostics)
            # cached: a second query must not re-run the probe
            monkeypatch.setitem(
                pg._PROBES, "flash_attention",
                lambda: (_ for _ in ()).throw(AssertionError("re-ran")))
            rep = pg.probe_report("flash_attention")
            assert rep["ok"] is False and rep["probed"] is True
            assert any(d["code"] == "TPU110"
                       for d in rep["diagnostics"])
            # the fallback is in the process log and on the timeline
            assert get_log().counts().get("TPU110", 0) >= 1
            names = [e.name for e in obs.get_timeline().events()]
            assert "lint:TPU110" in names
        finally:
            pg.reset_probe_cache()

    def test_pallas_disabled_off_tpu(self):
        from paddle_tpu.ops import pallas_gate as pg
        assert pg.pallas_enabled("flash_attention") is False

    def test_broken_probe_raises_on_tpu(self, monkeypatch):
        """On a TPU backend a failed probe stops the run with its
        diagnosis — first call and every later one — and only the flag
        selects the composites."""
        from paddle_tpu.ops import pallas_gate as pg

        def boom():
            raise ImportError("cannot import name 'disable_x64'")

        pg.reset_probe_cache()
        monkeypatch.setattr(pg.jax, "default_backend", lambda: "tpu")
        monkeypatch.setitem(pg._PROBES, "layer_norm", boom)
        try:
            for _ in range(2):
                with pytest.raises(RuntimeError,
                                   match="(?s)disable_x64.*TPU110"):
                    pg.pallas_enabled("layer_norm")
            paddle.set_flags({"FLAGS_use_pallas_kernels": False})
            assert pg.pallas_enabled("layer_norm") is False
        finally:
            paddle.set_flags({"FLAGS_use_pallas_kernels": True})
            pg.reset_probe_cache()


# ---------------------------------------------------------------------
# Recompile risk (TPU2xx)
# ---------------------------------------------------------------------
class TestRecompile:
    def test_python_scalar_churn(self):
        lin = nn.Linear(4, 4)

        def f(x, k):
            return (lin(x) * k).sum()

        traced = paddle.jit.to_static(f)
        x = paddle.randn([4, 4])
        for k in (1.0, 2.0, 3.0):
            traced(x, k)
        diags = analysis.audit_trace_cache(traced)
        assert "TPU203" in codes(diags)
        d = next(d for d in diags if d.code == "TPU203")
        assert d.data["variants"] == 3

    def test_shape_drift(self):
        lin = nn.Linear(4, 4)
        traced = paddle.jit.to_static(lambda x: lin(x).sum())
        for n in (2, 3, 5):
            traced(paddle.randn([n, 4]))
        assert "TPU202" in codes(analysis.audit_trace_cache(traced))

    def test_two_shapes_tolerated(self):
        # train vs eval batch is normal; below DRIFT_THRESHOLD no flag
        lin = nn.Linear(4, 4)
        traced = paddle.jit.to_static(lambda x: lin(x).sum())
        for n in (2, 3):
            traced(paddle.randn([n, 4]))
        assert analysis.audit_trace_cache(traced) == []

    def test_executor_cache_shape_drift(self):
        feed = lambda n: (("x", ((n, 64), "float32")),)
        cache = {(7, "fp0", feed(n), "fetch"): {"program_label": "prog"}
                 for n in (1, 2, 3)}
        diags = analysis.audit_executor_cache(cache)
        assert codes(diags) == ["TPU202"]

    def test_executor_cache_mutation(self):
        cache = {(7, fp, (("x", ((4, 4), "f32")),), "fetch"): {}
                 for fp in ("fp0", "fp1")}
        diags = analysis.audit_executor_cache(cache)
        assert codes(diags) == ["TPU204"]

    def test_eager_cache_fragmentation(self):
        cache = {("matmul", "c", (("0", f"V{i}"),), (), ((4, 4),)): None
                 for i in range(20)}
        diags = analysis.audit_eager_cache(cache, per_op_threshold=16)
        assert codes(diags) == ["TPU203"]
        assert "matmul" in diags[0].message

    def test_weak_type_input(self):
        jaxpr = jax.make_jaxpr(lambda x: x * 2)(1.0)
        diags = analysis.audit_weak_types(jaxpr, site="t")
        assert codes(diags) == ["TPU201"]


# ---------------------------------------------------------------------
# Host sync (TPU3xx)
# ---------------------------------------------------------------------
def _dispatch(ts, step):
    return Event("dispatch:prog", "dispatch", ts, dur=5.0, step=step)


def _read(ts, step, name="fetch.read"):
    return Event(name, "d2h", ts, dur=1.0, step=step)


class TestHostSync:
    def test_early_read_flagged(self):
        events = [_dispatch(0, 0), _read(50, 0), _dispatch(100, 1),
                  _read(150, 1), _dispatch(200, 2)]
        diags = audit_host_sync(events, budget=8)
        assert codes(diags) == ["TPU301"]
        assert diags[0].data["early_reads"] == 2

    def test_deferred_read_clean(self):
        # reads land after the NEXT dispatch: pipeline overlaps, no flag
        events = [_dispatch(0, 0), _dispatch(100, 1), _read(150, 0),
                  _dispatch(200, 2), _read(250, 1)]
        assert audit_host_sync(events, budget=8) == []

    def test_sync_budget(self):
        events = [_dispatch(0, 0), _dispatch(100, 1), _dispatch(200, 2)]
        events += [_read(210 + i, 1, f"metric{i}.read")
                   for i in range(5)]
        diags = audit_host_sync(events, budget=2)
        assert "TPU302" in codes(diags)
        d = next(d for d in diags if d.code == "TPU302")
        assert d.data == {"budget": 2, "steps_over": 1}

    def test_budget_env_override(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_LINT_SYNC_BUDGET", "7")
        assert analysis.sync_budget() == 7


# ---------------------------------------------------------------------
# Dtype / AMP audit (TPU4xx)
# ---------------------------------------------------------------------
class TestDtypeAudit:
    def test_amp_upcast(self):
        def f(x16, x32):
            a = jnp.dot(x16, x16)               # bf16 MXU op
            b = jnp.dot(x32, x32)               # escaped the white list
            return a.astype(jnp.float32) + b

        jaxpr = jax.make_jaxpr(f)(
            jnp.ones((8, 8), jnp.bfloat16), jnp.ones((8, 8), jnp.float32))
        diags = audit_jaxpr(jaxpr, amp="auto", site="t")
        assert "TPU401" in codes(diags)

    def test_uniform_precision_clean(self):
        jaxpr = jax.make_jaxpr(lambda x: jnp.dot(x, x))(
            jnp.ones((8, 8), jnp.bfloat16))
        assert audit_jaxpr(jaxpr, amp="auto") == []

    def test_f64_flagged(self):
        with jax.enable_x64(True):
            jaxpr = jax.make_jaxpr(
                lambda x: x.astype(jnp.float64).sum())(
                    jnp.ones((4,), jnp.float32))
        diags = audit_jaxpr(jaxpr, site="t")
        assert "TPU402" in codes(diags)

    def test_collective_payload_mismatch(self):
        diags = analysis.check_collective_payload(
            "all_reduce",
            [np.ones((4,), np.float32), np.ones((4,), np.float16)])
        assert codes(diags) == ["TPU403"]

    def test_collective_payload_f64(self):
        diags = analysis.check_collective_payload(
            "broadcast", [np.ones((4,), np.float64)])
        assert codes(diags) == ["TPU403"]

    def test_collective_payload_clean(self):
        assert analysis.check_collective_payload(
            "all_reduce", [np.ones((4,), np.float32)] * 2) == []


# ---------------------------------------------------------------------
# Entry points: Executor / to_static / diagnostics plumbing
# ---------------------------------------------------------------------
class TestEntryPoints:
    def test_executor_analyze_program_clean(self):
        from paddle_tpu import static
        paddle.enable_static()
        try:
            main, startup = static.Program(), static.Program()
            with static.program_guard(main, startup):
                x = static.data("x", [8, 4], "float32")
                y = static.data("y", [8, 1], "float32")
                lin = nn.Linear(4, 1)
                loss = F.mse_loss(lin(x), y)
                opt = optimizer.SGD(learning_rate=0.1,
                                    parameters=lin.parameters())
                opt.minimize(loss)
            exe = static.Executor()
            exe.run(startup)
            report = exe.analyze_program(
                main, feed={"x": np.ones((8, 4), np.float32),
                            "y": np.ones((8, 1), np.float32)},
                fetch_list=[loss])
            assert report.errors() == [], report.render()
        finally:
            paddle.disable_static()

    def test_traced_analyze_program(self):
        lin = nn.Linear(4, 4)

        def f(x, k):
            return (lin(x) * k).sum()

        traced = paddle.jit.to_static(f)
        x = paddle.randn([4, 4])
        for k in (1.0, 2.0):
            traced(x, k)
        report = traced.analyze_program(x, 2.0)
        assert report.errors() == []
        assert "TPU203" in report.counts()

    def test_traced_analyze_requires_trace(self):
        traced = paddle.jit.to_static(lambda x: x.sum())
        with pytest.raises(RuntimeError):
            traced.analyze_program()

    def test_record_reaches_log_and_timeline(self):
        with obs.enabled_scope():
            record(Diagnostic("TPU202", "synthetic drift", site="here"))
            events = obs.get_timeline().events()
        assert get_log().counts() == {"TPU202": 1}
        ev = next(e for e in events if e.name == "lint:TPU202")
        assert ev.cat == "analysis"
        assert ev.attrs["severity"] == "warning"

    def test_lint_summary_table(self):
        with obs.enabled_scope():
            record(Diagnostic("TPU301", "early read", site="loop"))
            record(Diagnostic("TPU301", "early read", site="loop"))
            record(Diagnostic("TPU101", "bad tile", site="k"))
            table = obs.lint_summary_table()
        assert "TPU301" in table and "TPU101" in table
        # errors sort above warnings regardless of count
        assert table.index("TPU101") < table.index("TPU301")

    def test_lint_summary_counts(self):
        record(Diagnostic("TPU402", "f64", site="t"))
        summary = analysis.lint_summary()
        assert summary["counts"].get("TPU402") == 1
        # every gated kernel's probe outcome is in the artifact, even
        # when nothing probed (all-fallback must not look like silence)
        from paddle_tpu.ops import pallas_gate as pg
        assert set(summary["pallas"]) == set(pg._PROBES)
        for rec in summary["pallas"].values():
            assert "probed" in rec

    def test_unknown_code_rejected(self):
        with pytest.raises(ValueError):
            Diagnostic("TPU999", "nope")

    def test_code_registry_shape(self):
        for code, (title, severity) in CODES.items():
            assert code.startswith("TPU") and len(code) == 6
            assert severity in ("error", "warning", "info")
            assert title


# ---------------------------------------------------------------------
# CLI gate over the bundled models (satellite d) — the tier-1 guard:
# a new error-severity diagnostic on lenet/bert/gpt fails this test.
# ---------------------------------------------------------------------
def _load_cli():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "tpu_lint.py")
    spec = importlib.util.spec_from_file_location("tpu_lint_cli", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestCLI:
    def test_models_lint_with_zero_errors(self):
        cli = _load_cli()
        assert cli.main(["--models", "--fail-on", "error"]) == 0

    def test_fail_on_error_catches_injected(self, capsys):
        cli = _load_cli()
        cli.LINTERS["__broken__"] = lambda: DiagnosticReport(
            [Diagnostic("TPU101", "injected", site="x")], label="b")
        try:
            rc = cli.main(["--models", "--only", "__broken__",
                           "--fail-on", "error"])
            assert rc == 1
            rc = cli.main(["--models", "--only", "__broken__",
                           "--fail-on", "never"])
            assert rc == 0
        finally:
            del cli.LINTERS["__broken__"]
        capsys.readouterr()


# ---------------------------------------------------------------------
# Fused training suite: block-plan audits + probe gate + smoke script
# ---------------------------------------------------------------------
class TestFusedSuitePlans:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("direction", ["fwd", "bwd_dq", "bwd_dkv"])
    def test_flash_bwd_plans_legal(self, dtype, direction):
        report = analysis.audit_flash_attention(
            batch=1, seq_q=128, seq_k=128, heads=4, head_dim=64,
            dtype=dtype, causal=True, direction=direction)
        assert list(report) == [], report.render()

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("direction", ["fwd", "bwd"])
    def test_ln_residual_plan_legal(self, dtype, direction):
        report = analysis.audit_layer_norm_residual(
            512, 768, dtype=dtype, direction=direction)
        assert list(report) == [], report.render()
        assert report.plan["block_rows"] % 8 == 0

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("direction", ["fwd", "bwd"])
    def test_ln_residual_dropout_plan_legal(self, dtype, direction):
        """The form that draws its mask, at the BERT cells' size: the
        seed rides as scalar prefetch, the backward writes d(x) beside
        d(residual), and the blocks stay legal and inside VMEM."""
        report = analysis.audit_layer_norm_residual(
            8192, 768, dtype=dtype, direction=direction, dropout=True)
        assert list(report) == [], report.render()
        plan = report.plan
        assert plan["scalar_prefetch"] == (("seed", (1,), jnp.int32),)
        assert "dropout" in report.label
        names = [o[0] for o in plan["operands"]]
        plain = analysis.audit_layer_norm_residual(
            8192, 768, dtype=dtype, direction=direction).plan
        assert plain["scalar_prefetch"] == ()
        extra = ["d_residual"] if direction == "bwd" else []
        assert sorted(names) == sorted(
            [o[0] for o in plain["operands"]] + extra)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("direction", ["fwd", "bwd"])
    def test_matmul_epilogue_plan_legal(self, dtype, direction):
        report = analysis.audit_matmul_epilogue(
            512, 768, 3072, dtype=dtype, direction=direction)
        assert list(report) == [], report.render()

    @pytest.mark.parametrize(
        "kernel", ["layer_norm_residual", "layer_norm_residual_dropout",
                   "matmul_epilogue"])
    def test_fused_kernels_force_probe_ok(self, kernel):
        # fwd AND bwd: both probes take a grad through the kernel
        from paddle_tpu.ops import pallas_gate as pg
        pg.reset_probe_cache()
        try:
            res = pg.probe_kernel(kernel, force=True)
            assert res.ok, res.error
            assert pg.probe_report(kernel)["ok"] is True
        finally:
            pg.reset_probe_cache()


def _load_fusion_smoke():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "fusion_smoke.py")
    spec = importlib.util.spec_from_file_location("fusion_smoke_cli", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.perf
class TestFusionSmoke:
    def test_all_suite_kernels_probe_ok(self, capsys):
        smoke = _load_fusion_smoke()
        ok, report = smoke.run()
        capsys.readouterr()
        assert ok, report
        # every gated kernel appears — no silent fallback
        from paddle_tpu.ops import pallas_gate as pg
        assert set(report) == set(pg._PROBES)
        assert all(rec["probed"] for rec in report.values())


def _load_lazy_smoke():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "lazy_smoke.py")
    spec = importlib.util.spec_from_file_location("lazy_smoke_cli", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.perf
class TestLazySmoke:
    def test_steady_state_lazy_step_is_fused_and_cached(self, capsys):
        from paddle_tpu.core import lazy
        smoke = _load_lazy_smoke()
        try:
            ok, report = smoke.run()
        finally:
            lazy.enable_lazy(False)
            lazy._tls.buffer.pending.clear()
            lazy._tls.buffer.donate.clear()
        capsys.readouterr()
        assert ok, report
        checks = report["checks"]
        # whole-step capture: <= 2 executable launches per train step
        assert checks["dispatch_per_step"]["value"] <= 2.0
        # fingerprinted reuse: steady state is a pure replay
        assert checks["segment_cache_hit_rate"]["value"] >= 0.9
        assert checks["steady_state_compiles"]["value"] == 0
