"""SPMD sharding suite (ISSUE 9): MeshPlan partition rules, mesh-keyed
executor/trace caches, DP/TP/FSDP parity on the forced 8-device host
mesh, per-shard preflight math, TPU5xx audits, DP serving, and the
sharding_smoke gate.

conftest.py forces an 8-device CPU host mesh before jax import, so
every plan here runs the same GSPMD partitioning path a real TPU slice
would — numerics: DP at pipeline depth 1 must be BIT-equal to
single-device on the first step (same per-example math, only the batch
is split); later steps may drift at float-rounding scale because GSPMD
reassociates the batch reduction.
"""
import os
import subprocess
import sys

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import static
from paddle_tpu.distributed.auto_parallel.sharding import (
    BERT_RULES, GPT_RULES, MeshPlan, annotate_params, clear_mesh_plan,
    match_partition_rules, parse_mesh_spec, set_mesh_plan)

pytestmark = pytest.mark.dist

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_plan():
    clear_mesh_plan()
    yield
    clear_mesh_plan()
    paddle.disable_static()


# ---------------------------------------------------------------------
# Rule matching
# ---------------------------------------------------------------------
class TestRules:
    def test_parse_mesh_spec(self):
        assert parse_mesh_spec("dp=4,tp=2") == {"dp": 4, "tp": 2}
        assert parse_mesh_spec({"fsdp": 8}) == {"fsdp": 8}
        with pytest.raises(ValueError):
            parse_mesh_spec("bogus=2")
        with pytest.raises(ValueError):
            parse_mesh_spec("dp=2,dp=2")
        with pytest.raises(ValueError):
            parse_mesh_spec("dp=0")
        with pytest.raises(ValueError):
            parse_mesh_spec("")

    def test_rule_miss_raises(self):
        rules = [(r"weight$", P("tp"))]
        with pytest.raises(ValueError,
                           match="Partition rule not found for param"):
            match_partition_rules(rules, {"encoder.bias": (64,)})

    def test_scalar_leaves_skip_matching(self):
        # scalars never shard and never require a rule
        out = match_partition_rules([], {"step": (), "one": (1,)})
        assert out == {"step": P(), "one": P()}

    def test_first_match_wins(self):
        rules = [(r"qkv\.weight$", P("fsdp", "tp")), (r".*", P())]
        out = match_partition_rules(
            rules, {"h.0.attn.qkv.weight": (64, 192),
                    "h.0.ln.weight": (64,)})
        assert out["h.0.attn.qkv.weight"] == P("fsdp", "tp")
        assert out["h.0.ln.weight"] == P()

    def test_builtin_rules_total_over_bundled_models(self):
        from paddle_tpu.models import (BertConfig, BertForMaskedLM,
                                       GPTConfig, GPTForCausalLM)
        paddle.seed(0)
        for rules, model in (
                (BERT_RULES(), BertForMaskedLM(BertConfig(
                    hidden_size=32, num_hidden_layers=1,
                    num_attention_heads=2, intermediate_size=64))),
                (GPT_RULES(), GPTForCausalLM(GPTConfig(
                    vocab_size=64, hidden_size=32, num_hidden_layers=1,
                    num_attention_heads=2, use_flash_attention=False,
                    max_position_embeddings=32)))):
            named = annotate_params(model)
            specs = match_partition_rules(
                rules, {n: tuple(p.shape) for n, p in named.items()})
            assert len(specs) == len(named)  # no miss raised


class TestLegalization:
    def test_absent_axis_dropped(self):
        plan = MeshPlan("tp=2", rules=[(r".*", P("fsdp", "tp"))],
                        virtual=True)
        assert plan.spec_for("w", (6, 8)) == P(None, "tp")

    def test_indivisible_dim_replicates(self):
        plan = MeshPlan("tp=2", rules=[(r".*", P(None, "tp"))],
                        virtual=True)
        assert plan.spec_for("w", (6, 7)) == P()

    def test_axis_used_at_most_once(self):
        plan = MeshPlan("tp=2", rules=[(r".*", P("tp", "tp"))],
                        virtual=True)
        assert plan.spec_for("w", (8, 8)) == P("tp")

    def test_batch_spec(self):
        plan = MeshPlan("dp=2,fsdp=2", virtual=True)
        assert plan.batch_spec((8, 16)) == P(("dp", "fsdp"))
        assert plan.batch_spec((6, 16)) == P()   # 6 % 4 != 0
        assert plan.batch_spec(()) == P()
        tp_only = MeshPlan("tp=2", virtual=True)
        assert tp_only.batch_spec((8, 16)) == P()


# ---------------------------------------------------------------------
# Training parity on the host mesh — the SAME program, unmodified,
# under each plan
# ---------------------------------------------------------------------
def _train_losses(mesh_spec, n_steps=3):
    from paddle_tpu import optimizer
    from paddle_tpu.models import BertConfig, BertForMaskedLM
    B, S = 8, 16
    paddle.enable_static()
    try:
        if mesh_spec is not None:
            set_mesh_plan(MeshPlan(mesh_spec, rules=BERT_RULES()))
        paddle.seed(0)
        main_prog, startup = static.Program(), static.Program()
        with static.program_guard(main_prog, startup):
            ids = static.data("ids", [B, S], "int64")
            labels = static.data("labels", [B, S], "int64")
            model = BertForMaskedLM(BertConfig(
                hidden_size=32, num_hidden_layers=2,
                num_attention_heads=2, intermediate_size=64))
            annotate_params(model)
            loss, _ = model(ids, labels=labels)
            opt = optimizer.AdamW(learning_rate=1e-3,
                                  parameters=model.parameters())
            opt.minimize(loss)
        exe = static.Executor()
        exe.run(startup)
        rng = np.random.default_rng(0)
        fd = {"ids": rng.integers(0, 100, (B, S)).astype(np.int64),
              "labels": rng.integers(0, 100, (B, S)).astype(np.int64)}
        return [float(exe.run(main_prog, feed=fd,
                              fetch_list=[loss])[0])
                for _ in range(n_steps)]
    finally:
        clear_mesh_plan()
        paddle.disable_static()


_baseline_cache = {}


def _baseline_losses():
    if "losses" not in _baseline_cache:
        _baseline_cache["losses"] = _train_losses(None)
    return _baseline_cache["losses"]


class TestTrainingParity:
    def test_dp_first_step_bitequal_at_depth1(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PIPELINE_DEPTH", "1")
        base = _baseline_losses()
        dp = _train_losses("dp=2")
        # depth 1, step 1: identical per-example math, batch merely
        # split — BIT equal, not approximately equal
        assert dp[0] == base[0]
        # later steps: GSPMD reassociates the batch-mean reduction;
        # float-rounding drift only
        np.testing.assert_allclose(dp, base, rtol=5e-4)

    def test_tp_matmul_parity(self):
        base = _baseline_losses()
        tp = _train_losses("tp=2")
        np.testing.assert_allclose(tp, base, rtol=1e-5)

    def test_fsdp_parity(self):
        base = _baseline_losses()
        fs = _train_losses("fsdp=2")
        np.testing.assert_allclose(fs, base, rtol=5e-4)

    def test_dp_tp_mixed_parity(self):
        base = _baseline_losses()
        mixed = _train_losses("dp=2,tp=2")
        np.testing.assert_allclose(mixed, base, rtol=5e-4)


# ---------------------------------------------------------------------
# Mesh-keyed executable caches
# ---------------------------------------------------------------------
class TestMeshKeyedCaches:
    def test_trace_cache_hit_and_miss(self):
        paddle.disable_static()

        def f(x):
            return (x * 2.0).sum()

        traced = paddle.jit.to_static(f)
        x = paddle.to_tensor(np.ones((8, 4), np.float32))
        traced(x)
        assert len(traced._cache) == 1
        set_mesh_plan(MeshPlan("dp=2"))
        traced(x)                      # plan switch -> new executable
        assert len(traced._cache) == 2
        traced(x)                      # same plan -> cache hit
        assert len(traced._cache) == 2
        clear_mesh_plan()
        traced(x)                      # back to the unsharded entry
        assert len(traced._cache) == 2

    def test_executor_cache_keyed_by_plan(self):
        # two plans over the same program produce two cache entries;
        # rerunning under a seen plan adds none
        static.Executor.clear_shared_cache()
        _train_losses("dp=2", n_steps=1)
        n_after_dp = len(static.Executor._shared_cache)
        assert n_after_dp >= 1
        _train_losses("tp=2", n_steps=1)
        assert len(static.Executor._shared_cache) > n_after_dp


# ---------------------------------------------------------------------
# Per-shard preflight math
# ---------------------------------------------------------------------
class TestPreflight:
    def test_per_device_nbytes(self):
        plan = MeshPlan("fsdp=2,tp=2", virtual=True)
        nb = 1 << 20
        assert plan.per_device_nbytes(nb, P("fsdp", "tp")) == nb // 4
        assert plan.per_device_nbytes(nb, P("fsdp")) == nb // 2
        assert plan.per_device_nbytes(nb, P()) == nb
        assert plan.shard_factor(None) == 1
        assert plan.shard_factor(P(("fsdp", "tp"))) == 4

    def test_entry_charges_sharded_residents_per_device(self):
        """Executor entry: every model resident (trainable param or
        frozen buffer) is charged its PER-DEVICE bytes — replicated
        size divided by the plan's shard factor.  named_buffers uses
        generated tensor names while spmd_named uses spmd names, so
        compare size multisets, not names."""
        static.Executor.clear_shared_cache()
        _train_losses("fsdp=2", n_steps=1)
        entry = next(e for e in static.Executor._shared_cache.values()
                     if e.get("plan") is not None)
        plan = entry["plan"]
        charged = sorted(
            v for k, v in dict(entry["named_buffers"]).items()
            if k.startswith(("param:", "frozen:")))
        expected = sorted(
            nbytes // plan.shard_factor(plan.spec_for(name, shape))
            for name, shape, nbytes in entry["spmd_named"])
        replicated = sorted(n for _, _, n in entry["spmd_named"])
        assert charged == expected
        # the plan genuinely shards: per-device footprint is <= 1/2
        # of replicated under fsdp=2 for sharded residents
        assert sum(charged) < sum(replicated)
        assert any(plan.shard_factor(plan.spec_for(n, s)) == 2
                   for n, s, _ in entry["spmd_named"])


# ---------------------------------------------------------------------
# TPU5xx audits
# ---------------------------------------------------------------------
class TestAudits:
    def test_tpu501_rule_miss_and_tpu502_large_replicated(self):
        from paddle_tpu.analysis.sharding_audit import audit_sharding
        plan = MeshPlan("tp=2", rules=[(r"qkv", P(None, "tp"))],
                        virtual=True)
        diags = audit_sharding(plan, [
            ("enc.qkv.weight", (64, 64), 64 * 64 * 4),
            ("enc.mystery.weight", (1024, 1024), 1024 * 1024 * 4),
        ])
        codes = sorted(d.code for d in diags)
        assert "TPU501" in codes
        # a matched-but-replicated large param under tp=2 is TPU502
        plan2 = MeshPlan("tp=2", rules=[(r".*", P())], virtual=True)
        diags2 = audit_sharding(plan2, [
            ("big.weight", (1024, 1024), 1024 * 1024 * 4)])
        assert [d.code for d in diags2] == ["TPU502"]

    def test_tpu502_threshold_env(self, monkeypatch):
        from paddle_tpu.analysis.sharding_audit import audit_sharding
        plan = MeshPlan("tp=2", rules=[(r".*", P())], virtual=True)
        big = [("w", (1024, 1024), 1024 * 1024 * 4)]
        monkeypatch.setenv("PADDLE_TPU_LINT_REPLICATED_BYTES",
                           str(1 << 30))
        assert audit_sharding(plan, big) == []

    def test_tpu504_ragged_tokens(self):
        from paddle_tpu.analysis.sharding_audit import audit_overlap
        plan = MeshPlan("tp=2", rules=[(r".*", P("tp", None))],
                        virtual=True)
        inv = [("enc.fc2.weight", (64, 32), 64 * 32 * 4)]
        assert audit_overlap(plan, inv, tokens_hint=128) == []
        diags = audit_overlap(plan, inv, tokens_hint=129)
        assert [d.code for d in diags] == ["TPU504"]
        # the tile arithmetic is shown, not just asserted
        assert "129 % 2" in diags[0].message
        assert diags[0].data["reason"] == "ragged"

    def test_tpu504_overlap_forced_off(self, monkeypatch):
        from paddle_tpu.analysis.sharding_audit import audit_overlap
        plan = MeshPlan("tp=2", rules=[(r".*", P("tp", None))],
                        virtual=True)
        inv = [("enc.fc2.weight", (64, 32), 64 * 32 * 4)]
        monkeypatch.setenv("PADDLE_TPU_OVERLAP", "sequential")
        diags = audit_overlap(plan, inv, tokens_hint=128)
        assert [d.code for d in diags] == ["TPU504"]
        assert diags[0].data["reason"] == "flag"
        # no tp axis -> nothing to overlap, no diagnostic
        dp_plan = MeshPlan("dp=2", rules=[(r".*", P())], virtual=True)
        assert audit_overlap(dp_plan, inv, tokens_hint=129) == []

    def test_tpu503_indivisible_payload(self):
        from paddle_tpu.analysis.sharding_audit import \
            check_collective_axis
        bad = np.zeros((7, 4), np.float32)
        good = np.zeros((8, 4), np.float32)
        diags = check_collective_axis("reduce_scatter", [bad, good], 2)
        assert [d.code for d in diags] == ["TPU503"]
        # gather-class ops don't split the payload
        assert check_collective_axis("allreduce", [bad], 2) == []

    def test_lint_cli_sharding_model(self):
        import importlib.util
        path = os.path.join(ROOT, "scripts", "tpu_lint.py")
        spec = importlib.util.spec_from_file_location("tpu_lint_sh",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert "sharding" in mod.MODELS
        assert mod.main(["--models", "--only", "sharding",
                         "--fail-on", "warning"]) == 0


# ---------------------------------------------------------------------
# DP serving
# ---------------------------------------------------------------------
class TestServingDP:
    def test_dp_engine_matches_single_and_reports_shards(self):
        from paddle_tpu import observability as obs
        from paddle_tpu.inference.serving import (DataParallelEngine,
                                                  GenerationEngine)
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

        paddle.disable_static()
        cfg = GPTConfig(vocab_size=97, hidden_size=32,
                        num_hidden_layers=2, num_attention_heads=4,
                        max_position_embeddings=64)
        paddle.seed(7)
        model = GPTForCausalLM(cfg)
        model.eval()
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, 97, size=n).tolist()
                   for n in (5, 7, 4)]

        ref = GenerationEngine(model, num_blocks=64, max_batch=4)
        try:
            expected = ref.generate(prompts, max_new_tokens=4)
        finally:
            ref.close()

        obs.enable(True)
        obs.get_timeline().clear()
        dp = DataParallelEngine(model, dp=2, num_blocks=64,
                                max_batch=4)
        try:
            got = dp.generate(prompts, max_new_tokens=4)
            st = dp.stats()
        finally:
            dp.close()
        assert got == expected
        assert st["dp"] == 2
        assert set(st["per_shard"]) == {"dp0", "dp1"}
        # both replicas did work (least-loaded dispatch over 3 reqs)
        assert all(s["tokens_generated"] > 0
                   for s in st["per_shard"].values())

        pb = obs.phase_breakdown()
        assert set(pb.get("shards", {})) == {"dp0", "dp1"}
        ps = obs.pipeline_stats()
        assert set(ps.get("per_shard", {})) == {"dp0", "dp1"}

    def test_dp_from_active_plan(self):
        from paddle_tpu.inference.serving import DataParallelEngine
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        paddle.disable_static()
        cfg = GPTConfig(vocab_size=64, hidden_size=32,
                        num_hidden_layers=1, num_attention_heads=2,
                        max_position_embeddings=32)
        paddle.seed(1)
        model = GPTForCausalLM(cfg)
        model.eval()
        set_mesh_plan(MeshPlan("dp=2"))
        dp = DataParallelEngine(model, num_blocks=16, max_batch=2)
        try:
            assert dp.dp == 2
        finally:
            dp.close()


# ---------------------------------------------------------------------
# Overlapped sharded matmuls (ISSUE 11 tentpole)
# ---------------------------------------------------------------------
class TestOverlappedMatmul:
    def _mats(self, m, k, n, dtype=np.float32, seed=0):
        rng = np.random.default_rng(seed)
        return (rng.standard_normal((m, k)).astype(dtype),
                rng.standard_normal((k, n)).astype(dtype))

    @staticmethod
    def _assert_f32_dot(out, a, b):
        """`out` vs NumPy's `a @ b`, to the float32 forward-error bound
        K*eps*(|a| @ |b|).  Not 1e-6 relative: NumPy's BLAS sums K in
        another order than XLA's CPU dot on jaxlib 0.9, and results
        that are small by cancellation then differ by up to 3e-5
        relative (6e-7 absolute).  The ring itself is bit-equal to
        XLA's unsharded `jnp.dot` and the closer of the two to the
        float64 product."""
        bound = a.shape[1] * np.finfo(np.float32).eps * (
            np.abs(a) @ np.abs(b))
        assert (np.abs(out - a @ b) <= bound).all()

    def test_ag_f32_bitexact_vs_sequential(self):
        from paddle_tpu.distributed.auto_parallel.overlap import \
            sharded_matmul
        plan = MeshPlan("tp=4", rules={})
        a, b = self._mats(32, 16, 8)
        ov = np.asarray(sharded_matmul(a, b, direction="ag", plan=plan,
                                       mode="overlap"))
        sq = np.asarray(sharded_matmul(a, b, direction="ag", plan=plan,
                                       mode="sequential"))
        assert np.array_equal(ov, sq)
        self._assert_f32_dot(ov, a, b)

    def test_rs_f32_bitexact_vs_sequential(self):
        from paddle_tpu.distributed.auto_parallel.overlap import \
            sharded_matmul
        plan = MeshPlan("tp=4", rules={})
        a, b = self._mats(16, 32, 8, seed=1)
        ov = np.asarray(sharded_matmul(a, b, direction="rs", plan=plan,
                                       mode="overlap"))
        sq = np.asarray(sharded_matmul(a, b, direction="rs", plan=plan,
                                       mode="sequential"))
        assert np.array_equal(ov, sq)
        # vs the unsharded dot the k-split accumulation order differs:
        # float-rounding scale only
        np.testing.assert_allclose(ov, a @ b, rtol=1e-4)

    def test_bf16_both_directions(self):
        import jax.numpy as jnp
        from paddle_tpu.distributed.auto_parallel.overlap import \
            sharded_matmul
        plan = MeshPlan("tp=4", rules={})
        a32, b32 = self._mats(32, 16, 8, seed=2)
        a = jnp.asarray(a32, jnp.bfloat16)
        b = jnp.asarray(b32, jnp.bfloat16)
        for direction in ("ag", "rs"):
            ov = sharded_matmul(a, b, direction=direction, plan=plan,
                                mode="overlap")
            sq = sharded_matmul(a, b, direction=direction, plan=plan,
                                mode="sequential")
            assert ov.dtype == jnp.bfloat16
            # both modes accumulate in f32 and cast once at the end,
            # so tile count never changes the bf16 result
            assert np.array_equal(np.asarray(ov, np.float32),
                                  np.asarray(sq, np.float32))
            ref = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
            np.testing.assert_allclose(np.asarray(ov, np.float32),
                                       ref, rtol=5e-2, atol=0.5)

    def test_uneven_last_tiles_padded(self):
        # 30 and 18 don't divide by tp=4: the wrapper zero-pads to the
        # tile grid and slices back — same numbers as the even case
        from paddle_tpu.distributed.auto_parallel.overlap import \
            sharded_matmul
        plan = MeshPlan("tp=4", rules={})
        a, b = self._mats(30, 18, 12, seed=3)
        for direction in ("ag", "rs"):
            ov = np.asarray(sharded_matmul(a, b, direction=direction,
                                           plan=plan, mode="overlap"))
            sq = np.asarray(sharded_matmul(a, b, direction=direction,
                                           plan=plan,
                                           mode="sequential"))
            assert ov.shape == (30, 12)
            assert np.array_equal(ov, sq)
            np.testing.assert_allclose(ov, a @ b, rtol=1e-4,
                                       atol=1e-6)

    def test_measured_driver_overlap_ratio(self):
        from paddle_tpu import observability as obs
        from paddle_tpu.distributed.auto_parallel.overlap import \
            measured_sharded_matmul
        plan = MeshPlan("tp=4", rules={})
        a, b = self._mats(32, 16, 8, seed=4)
        obs.enable(True)
        obs.get_timeline().clear()
        out = np.asarray(measured_sharded_matmul(a, b, plan=plan,
                                                 mode="overlap"))
        self._assert_f32_dot(out, a, b)
        stats = obs.collective_overlap_stats()
        assert stats["tp"]["overlap_ratio"] > 0
        assert stats["tp"]["count"] == 3      # P-1 ring hops
        pb = obs.phase_breakdown()
        assert pb["overlap_ratio_tp"] == stats["tp"]["overlap_ratio"]
        assert obs.pipeline_stats()["overlap"]["tp"]["overlap_ratio"] \
            == stats["tp"]["overlap_ratio"]
        # sequential driver on a fresh timeline: the hop is blocked on
        # before the dot dispatches, so nothing hides under compute
        obs.get_timeline().clear()
        measured_sharded_matmul(a, b, plan=plan, mode="sequential")
        seq = obs.collective_overlap_stats()
        assert seq["tp"]["overlap_ratio"] < \
            stats["tp"]["overlap_ratio"]

    def test_executor_routes_overlapped_matmuls(self):
        # the static executor's op_override sends row-parallel linear
        # ops through the ring decomposition; the entry records which
        static.Executor.clear_shared_cache()
        _train_losses("tp=2", n_steps=1)
        entry = next(e for e in static.Executor._shared_cache.values()
                     if e.get("plan") is not None)
        assert entry["overlap_mode"] == "overlap"
        routed = entry["overlap_routed"]
        assert len(routed) == 4       # attention.out + fc2, 2 layers
        assert all(n.endswith((".attention.out.weight", ".fc2.weight"))
                   for n in routed)

    def test_overlap_flag_forces_sequential(self, monkeypatch):
        from paddle_tpu.distributed.auto_parallel.overlap import \
            select_mode
        monkeypatch.setenv("PADDLE_TPU_OVERLAP", "sequential")
        plan = MeshPlan("tp=2", rules={})
        assert select_mode(plan) == "sequential"
        monkeypatch.setenv("PADDLE_TPU_OVERLAP", "overlap")
        assert select_mode(plan) == "overlap"
        # no model axis -> nothing to overlap even when forced on
        assert select_mode(MeshPlan("dp=2", rules={})) == "sequential"


# ---------------------------------------------------------------------
# Pipeline parallelism: the pp axis + 1F1B schedule (ISSUE 11)
# ---------------------------------------------------------------------
def _two_stage_mlp():
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    w1 = jnp.asarray(rng.standard_normal((8, 16)).astype(np.float32))
    w2 = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))

    def s0(params, x):
        return jnp.tanh(x @ params["w"])

    def s1(params, x):
        return x @ params["w"]

    def loss_fn(pred, y):
        return jnp.mean((pred - y) ** 2)

    return [s0, s1], [{"w": w1}, {"w": w2}], loss_fn


class TestPipelineParallel:
    def test_parse_pp_axis_and_stage_plans(self):
        assert parse_mesh_spec("dp=2;pp=2") == {"dp": 2, "pp": 2}
        assert parse_mesh_spec("pp=4") == {"pp": 4}
        plan = MeshPlan("dp=2,pp=2", rules={})
        assert plan.num_stages == 2
        for s in range(2):
            sub = plan.stage_plan(s)
            assert sub is not None and sub.axis_sizes == {"dp": 2}
            # 4 mesh devices / 2 stages -> 2 devices per stage slice
            assert len(plan.stage_devices(s)) == 2
        # device slices of distinct stages don't intersect
        d0 = {str(d) for d in plan.stage_devices(0)}
        d1 = {str(d) for d in plan.stage_devices(1)}
        assert not (d0 & d1)
        # pp-only plan: stage sub-plan degenerates to a single device
        pp_only = MeshPlan("pp=2", rules={})
        assert pp_only.stage_plan(0) is None
        assert len(pp_only.stage_devices(0)) == 1

    def test_one_f_one_b_order_properties(self):
        from paddle_tpu.distributed.auto_parallel.pipeline import (
            max_in_flight, one_f_one_b_order)
        for S, M in ((1, 3), (2, 4), (4, 8), (3, 2)):
            order = one_f_one_b_order(S, M)
            fwd_seen = [set() for _ in range(S)]
            bwd_seen = [set() for _ in range(S)]
            for kind, s, m in order:
                if kind == "F":
                    if s > 0:        # upstream stage forwarded m first
                        assert m in fwd_seen[s - 1]
                    fwd_seen[s].add(m)
                else:
                    assert m in fwd_seen[s]
                    if s < S - 1:    # downstream stage backpropped m
                        assert m in bwd_seen[s + 1]
                    bwd_seen[s].add(m)
            assert all(len(f) == M for f in fwd_seen)
            assert all(len(b) == M for b in bwd_seen)
            peaks = max_in_flight(order, S)
            assert all(peaks[s] <= min(M, S - s) for s in range(S))

    def test_1f1b_parity_vs_full_batch(self):
        import jax
        from paddle_tpu.distributed.auto_parallel.pipeline import \
            PipelineSchedule
        stages, params, loss_fn = _two_stage_mlp()
        rng = np.random.default_rng(1)
        x = np.asarray(rng.standard_normal((8, 8)), np.float32)
        y = np.asarray(rng.standard_normal((8, 8)), np.float32)
        sched = PipelineSchedule(stages, params, loss_fn,
                                 plan=MeshPlan("pp=2", rules={}),
                                 num_microbatches=4)
        loss, grads = sched.step(x, y)

        def full(p0, p1, xv, yv):
            return loss_fn(stages[1](p1, stages[0](p0, xv)), yv)

        ref_loss, ref_grads = jax.value_and_grad(
            full, argnums=(0, 1))(params[0], params[1], x, y)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-6)
        # grads: microbatch summation order differs from the
        # full-batch reduction; float-rounding drift only
        for got, want in zip(grads, ref_grads):
            np.testing.assert_allclose(np.asarray(got["w"]),
                                       np.asarray(want["w"]),
                                       rtol=1e-4, atol=1e-7)

    def test_1f1b_pp1_matches_pp2(self):
        from paddle_tpu.distributed.auto_parallel.pipeline import \
            PipelineSchedule
        stages, params, loss_fn = _two_stage_mlp()
        rng = np.random.default_rng(2)
        x = np.asarray(rng.standard_normal((8, 8)), np.float32)
        y = np.asarray(rng.standard_normal((8, 8)), np.float32)
        l2, g2 = PipelineSchedule(
            stages, params, loss_fn, plan=MeshPlan("pp=2", rules={}),
            num_microbatches=4).step(x, y)
        l1, g1 = PipelineSchedule(
            stages, params, loss_fn, plan=None,
            num_microbatches=4).step(x, y)
        np.testing.assert_allclose(float(l2), float(l1), rtol=1e-6)
        for a, b in zip(g2, g1):
            np.testing.assert_allclose(np.asarray(a["w"]),
                                       np.asarray(b["w"]), rtol=1e-5)

    def test_preflight_microbatch_line_item(self):
        from paddle_tpu.distributed.auto_parallel.pipeline import \
            PipelineSchedule
        stages, params, loss_fn = _two_stage_mlp()
        sched = PipelineSchedule(stages, params, loss_fn,
                                 plan=MeshPlan("pp=2", rules={}),
                                 num_microbatches=4)
        x = np.zeros((8, 8), np.float32)
        est = sched.preflight(x, raise_on_over=False)
        assert est is not None
        names = [n for n, _ in est.buffers]
        assert "pp microbatch in-flight buffers" in names
        assert "pp stage 0 residents" in names
        assert "pp stage 1 residents" in names
        mb = dict(est.buffers)["pp microbatch in-flight buffers"]
        assert mb == sched.microbatch_buffer_bytes(
            np.zeros((2, 8), np.float32))
        assert mb > 0

    def test_cache_token_tracks_pp_and_overlap_mode(self, monkeypatch):
        base = MeshPlan("dp=2", rules={}, virtual=True)
        with_pp = MeshPlan("dp=2,pp=2", rules={}, virtual=True)
        assert base.cache_token() != with_pp.cache_token()
        tok = base.cache_token()
        monkeypatch.setenv("PADDLE_TPU_OVERLAP", "sequential")
        assert base.cache_token() != tok
        monkeypatch.delenv("PADDLE_TPU_OVERLAP")
        assert base.cache_token() == tok


# ---------------------------------------------------------------------
# The smoke gate
# ---------------------------------------------------------------------
class TestSmokeScript:
    def test_sharding_smoke_passes(self):
        env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
        p = subprocess.run(
            [sys.executable,
             os.path.join(ROOT, "scripts", "sharding_smoke.py")],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=ROOT)
        assert p.returncode == 0, p.stderr[-2000:]
        assert "SHARDING_SMOKE_OK" in p.stdout
