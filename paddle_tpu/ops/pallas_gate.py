"""Runtime gate for the Pallas hot kernels: kill-switch + probe.

Role of the reference's kernel-selection guards (KernelFactory picking a
GPU kernel vs a fallback, `FLAGS_*` kill switches read by the dispatch
layer — SURVEY.md §2.1 "Flags/enforce", upstream `paddle/common/flags.*`
[UNVERIFIED — empty reference mount]).

Every Pallas call site asks `pallas_enabled(name)` instead of testing
`jax.default_backend()` directly.  The gate:

  1. reads ``FLAGS_use_pallas_kernels`` on every call, so
     ``paddle.set_flags({'FLAGS_use_pallas_kernels': False})`` (or the
     env var) is the explicit way to run the XLA composites;
  2. the first time each kernel is about to be used on a real TPU,
     probe-compiles it (fwd+bwd at a tiny shape) and caches the result.

On a TPU a failed probe is an error, not a quieter path: the run stops
with the diagnosis — the Mosaic error and any static tiling findings
(``analysis.tiling`` over the kernel's block plan), also cached in a
``ProbeResult``, queryable via ``probe_report()``, recorded to the
analysis diagnostic log and emitted as a ``cat="analysis"`` instant.  A
fallback here once switched every kernel off for a whole process
without a word (an import that jax had moved), and a chip run would
have timed no kernel this repo wrote and still exited 0.

On non-TPU backends ``pallas_enabled`` returns False (call sites use
the XLA composite; the kernels themselves are still exercised in
interpret mode by tests/test_pallas_kernels.py).  ``probe_kernel(name,
force=True)`` runs a probe anyway — in interpret mode — and returns a
failed ``ProbeResult`` instead of raising, so the CLI and tests
exercise the full diagnosis path off-hardware.
"""
from __future__ import annotations

import logging
import traceback

import jax
import jax.numpy as jnp

__all__ = ["pallas_enabled", "probe_kernel", "probe_report",
           "reset_probe_cache", "ProbeResult"]

_logger = logging.getLogger("paddle_tpu.pallas")

# kernel name -> ProbeResult (populated lazily, cleared by reset)
_probe_results: dict = {}


class ProbeResult:
    """Outcome of one kernel probe compile, with failure diagnosis."""

    __slots__ = ("kernel", "ok", "error", "error_type", "diagnostics")

    def __init__(self, kernel, ok, error=None, error_type=None,
                 diagnostics=()):
        self.kernel = kernel
        self.ok = ok
        self.error = error
        self.error_type = error_type
        self.diagnostics = list(diagnostics)

    def to_dict(self):
        d = {"kernel": self.kernel, "ok": self.ok, "probed": True}
        if not self.ok:
            d["error"] = self.error
            d["error_type"] = self.error_type
            d["diagnostics"] = [x.to_dict() for x in self.diagnostics]
        return d


def _flag_on() -> bool:
    from ..framework.flags import get_flags
    return bool(get_flags("FLAGS_use_pallas_kernels")
                ["FLAGS_use_pallas_kernels"])


def _probe_flash_attention(dropout_p=0.0):
    from . import pallas_kernels as pk
    q = jnp.zeros((1, 128, 1, 64), jnp.bfloat16)
    seed = jnp.zeros((1,), jnp.int32) if dropout_p else None
    fn = jax.jit(jax.grad(
        lambda q, k, v: pk.flash_attention(
            q, k, v, causal=True, dropout_p=dropout_p,
            seed=seed).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))
    jax.block_until_ready(fn(q, q, q))


def _probe_flash_attention_dropout():
    """The kernels with the generator in them (per-tile reseeding)."""
    _probe_flash_attention(dropout_p=0.1)


def _probe_layer_norm():
    from . import pallas_kernels as pk
    x = jnp.zeros((32, 256), jnp.bfloat16)
    g = jnp.ones((256,), jnp.bfloat16)
    fn = jax.jit(jax.grad(
        lambda x, g, b: pk.fused_layer_norm(
            x, g, b).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    jax.block_until_ready(fn(x, g, g))


def _probe_rms_norm():
    from . import pallas_kernels as pk
    x = jnp.zeros((32, 256), jnp.bfloat16)
    g = jnp.ones((256,), jnp.bfloat16)
    fn = jax.jit(jax.grad(
        lambda x, g: pk.fused_rms_norm(x, g).astype(jnp.float32).sum(),
        argnums=(0, 1)))
    jax.block_until_ready(fn(x, g))


def _probe_softmax_cross_entropy():
    from . import pallas_kernels as pk
    x = jnp.zeros((32, 512), jnp.float32)
    lbl = jnp.zeros((32,), jnp.int32)
    fn = jax.jit(jax.grad(
        lambda x: pk.fused_softmax_cross_entropy(x, lbl).sum()))
    jax.block_until_ready(fn(x))


def _probe_layer_norm_residual(dropout_p=0.0):
    from . import pallas_fused as pf
    x = jnp.zeros((32, 256), jnp.bfloat16)
    g = jnp.ones((256,), jnp.bfloat16)
    seed = jnp.zeros((1,), jnp.int32) if dropout_p else None
    fn = jax.jit(jax.grad(
        lambda x, r, g, b: pf.fused_layer_norm_residual(
            x, r, g, b, dropout_p=dropout_p,
            seed=seed).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3)))
    jax.block_until_ready(fn(x, x, g, g))


def _probe_layer_norm_residual_dropout():
    """The kernels with the generator in them (per-block reseeding)."""
    _probe_layer_norm_residual(dropout_p=0.1)


def _probe_matmul_epilogue():
    from . import pallas_fused as pf
    x = jnp.zeros((32, 128), jnp.bfloat16)
    w = jnp.ones((128, 256), jnp.bfloat16)
    b = jnp.zeros((256,), jnp.bfloat16)
    fn = jax.jit(jax.grad(
        lambda x, w, b: pf.fused_linear_act(
            x, w, b, "gelu_tanh").astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))
    jax.block_until_ready(fn(x, w, b))


def _probe_matmul_epilogue_int8():
    from . import pallas_fused as pf
    x = jnp.zeros((32, 128), jnp.bfloat16)
    w_q = jnp.ones((128, 256), jnp.int8)
    s = jnp.ones((256,), jnp.float32)
    b = jnp.zeros((256,), jnp.bfloat16)
    fn = jax.jit(jax.grad(
        lambda x, s, b: pf.fused_linear_act_int8(
            x, w_q, s, b, "gelu_tanh").astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))
    jax.block_until_ready(fn(x, s, b))


def _probe_grouped_matmul():
    from . import pallas_grouped as pg
    from . import pallas_tiles as pt
    E, K, N, tokens = 2, 128, 256, 48
    bm, nb, rows = pg.grouped_layout(tokens, E, jnp.bfloat16)
    gid, _ = pt.group_segments(jnp.array([tokens - 16, 16], jnp.int32),
                               bm, nb)
    x = jnp.zeros((rows, K), jnp.bfloat16)
    w = jnp.ones((E, K, N), jnp.bfloat16)
    b = jnp.zeros((E, N), jnp.bfloat16)
    fn = jax.jit(jax.grad(
        lambda x, w, b: pg.grouped_linear_act(
            x, w, b, block_group=gid,
            act="gelu_tanh").astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))
    jax.block_until_ready(fn(x, w, b))


def _probe_lora_sgmv():
    from . import pallas_grouped as pg
    L, K, N, r = 2, 128, 256, 8
    bm = 16                       # bf16 sublane multiple
    nb = 3
    aid = jnp.array([0, L, 1], jnp.int32)   # middle block null
    z = jnp.zeros((nb * bm, N), jnp.bfloat16)
    x = jnp.zeros((nb * bm, K), jnp.bfloat16)
    a = jnp.ones((L, K, pg.lora_rank_pad(r, jnp.bfloat16)), jnp.bfloat16)
    b = jnp.ones((L, a.shape[2], N), jnp.bfloat16)
    fn = jax.jit(jax.grad(
        lambda z, x, a, b: pg.lora_segment_epilogue(
            z, x, a, b, block_adapter=aid,
            act="gelu_tanh").astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3)))
    jax.block_until_ready(fn(z, x, a, b))


def _probe_ragged_attention():
    from . import pallas_ragged as pr
    block_q = pr.ragged_q_block(jnp.float32)
    nqb = 3                       # one 2-block prefill + one decode
    q = jnp.zeros((nqb * block_q, 2, 64), jnp.float32)
    pool = jnp.zeros((4, 16, 2 * 64), jnp.float32)
    # a table wider than either context: the walk inside the program
    # has slots to leave out
    bt = jnp.array([[1, 2, 0, 0], [3, 0, 0, 0]], jnp.int32)
    cl = jnp.array([20, 5], jnp.int32)
    sid = jnp.array([0, 0, 1], jnp.int32)
    qs = jnp.array([4, 4 + block_q, 4], jnp.int32)
    qv = jnp.array([block_q, block_q, 1], jnp.int32)
    fn = jax.jit(lambda q, kp, vp: pr.ragged_paged_attention(
        q, kp, vp, bt, cl, sid, qs, qv, block_q=block_q))
    jax.block_until_ready(fn(q, pool, pool))


def _probe_ragged_attention_int8():
    from . import pallas_ragged as pr
    block_q = pr.ragged_q_block(jnp.float32)
    nqb = 3                       # one 2-block prefill + one decode
    q = jnp.zeros((nqb * block_q, 2, 64), jnp.float32)
    pool = jnp.zeros((4, 16, 2 * 64), jnp.int8)
    scales = jnp.ones((4, 16, pr.KV_SCALE_LANES), jnp.float32)
    # a table wider than either context: the walk inside the program
    # has slots to leave out
    bt = jnp.array([[1, 2, 0, 0], [3, 0, 0, 0]], jnp.int32)
    cl = jnp.array([20, 5], jnp.int32)
    sid = jnp.array([0, 0, 1], jnp.int32)
    qs = jnp.array([4, 4 + block_q, 4], jnp.int32)
    qv = jnp.array([block_q, block_q, 1], jnp.int32)
    fn = jax.jit(lambda q, kp, vp, ks, vs: pr.ragged_paged_attention(
        q, kp, vp, bt, cl, sid, qs, qv, block_q=block_q,
        k_scales=ks, v_scales=vs))
    jax.block_until_ready(fn(q, pool, pool, scales, scales))


def _probe_lightning_attention():
    from . import pallas_lightning as pll
    slopes = tuple(float(x) for x in pll.decay_slopes(4))
    q = jnp.zeros((256, 4, 128), jnp.bfloat16)
    pool = jnp.zeros((3, 4, 128, 128), jnp.float32)
    fn = jax.jit(lambda q, pool: pll.lightning_attention_step(
        q[:2], q[:2], q[:2],
        pll.lightning_attention_fwd(q, q, q, pool, 1, 200, 1, slopes)[1],
        jnp.array([1, 2], jnp.int32), slopes))
    jax.block_until_ready(fn(q, pool))


def _probe_gated_delta_rule():
    from . import pallas_gated_delta as pgd
    q = jnp.zeros((128, 2, 128), jnp.bfloat16)
    v = jnp.zeros((128, 4, 128), jnp.bfloat16)
    g = jnp.zeros((128, 4), jnp.float32)
    pool = jnp.zeros((3, 4, 128, 128), jnp.float32)
    fn = jax.jit(lambda q, v, g, pool: pgd.gated_delta_rule_step_fwd(
        q[:2], q[:2], v[:2], g[:2], g[:2],
        pgd.gated_delta_rule_fwd(q, q, v, g, g, pool, 1, 100, 1)[1],
        jnp.array([1, 2], jnp.int32)))
    jax.block_until_ready(fn(q, v, g, pool))


def _probe_sparse_select():
    from . import pallas_sparse as pls
    sizes = pls.SparseSizes()
    q = jnp.zeros((2, 32, 128), jnp.bfloat16)
    ck = jnp.zeros((3, 2, 256, 128), jnp.bfloat16)
    fn = jax.jit(lambda q, ck: pls.sparse_select_scores(
        q, ck, jnp.array([1, 2], jnp.int32),
        jnp.array([500, -1], jnp.int32), sizes, use_pallas=True))
    jax.block_until_ready(fn(q, ck))


_PROBES = {
    "flash_attention": _probe_flash_attention,
    "flash_attention_dropout": _probe_flash_attention_dropout,
    "ragged_attention": _probe_ragged_attention,
    "ragged_attention_int8": _probe_ragged_attention_int8,
    "layer_norm": _probe_layer_norm,
    "layer_norm_residual": _probe_layer_norm_residual,
    "layer_norm_residual_dropout": _probe_layer_norm_residual_dropout,
    "lightning_attention": _probe_lightning_attention,
    "gated_delta_rule": _probe_gated_delta_rule,
    "sparse_select": _probe_sparse_select,
    "grouped_matmul": _probe_grouped_matmul,
    "lora_sgmv": _probe_lora_sgmv,
    "matmul_epilogue": _probe_matmul_epilogue,
    "matmul_epilogue_int8": _probe_matmul_epilogue_int8,
    "rms_norm": _probe_rms_norm,
    "softmax_cross_entropy": _probe_softmax_cross_entropy,
}


def _static_diagnose(kernel):
    """Static tiling audit of the kernel's block plan at probe shape —
    attributes a Mosaic failure to a concrete TPU1xx rule when one is
    violated (plan shapes mirror the _probe_* functions above)."""
    from ..analysis import tiling
    if kernel in ("flash_attention", "flash_attention_dropout"):
        diags = []
        for direction in ("fwd", "bwd_dq", "bwd_dkv"):
            diags.extend(tiling.audit_flash_attention(
                1, 128, 128, 1, 64, dtype=jnp.bfloat16, causal=True,
                direction=direction))
        return diags
    if kernel == "ragged_attention":
        return list(tiling.audit_ragged_attention(
            2, 64, 16, num_q_blocks=3, num_blocks=4, table_width=4,
            dtype=jnp.float32))
    if kernel == "ragged_attention_int8":
        return list(tiling.audit_ragged_attention(
            2, 64, 16, num_q_blocks=3, num_blocks=4, table_width=4,
            dtype=jnp.float32, kv_dtype=jnp.int8))
    if kernel in ("layer_norm_residual", "layer_norm_residual_dropout"):
        diags = []
        for direction in ("fwd", "bwd"):
            diags.extend(tiling.audit_layer_norm_residual(
                32, 256, dtype=jnp.bfloat16, direction=direction,
                dropout=kernel.endswith("_dropout")))
        return diags
    if kernel == "grouped_matmul":
        diags = []
        for direction in ("fwd", "bwd_dw"):
            diags.extend(tiling.audit_grouped_matmul(
                48, 128, 256, 2, dtype=jnp.bfloat16,
                direction=direction))
        return diags
    if kernel == "lora_sgmv":
        diags = []
        for direction in ("fwd", "bwd_dw"):
            diags.extend(tiling.audit_lora_sgmv(
                48, 128, 256, 8, 2, dtype=jnp.bfloat16,
                direction=direction))
        return diags
    if kernel == "matmul_epilogue":
        diags = []
        for direction in ("fwd", "bwd"):
            diags.extend(tiling.audit_matmul_epilogue(
                32, 128, 256, dtype=jnp.bfloat16, direction=direction))
        return diags
    if kernel == "matmul_epilogue_int8":
        diags = []
        for direction in ("fwd", "bwd"):
            diags.extend(tiling.audit_matmul_epilogue(
                32, 128, 256, dtype=jnp.bfloat16, direction=direction,
                weight_dtype=jnp.int8))
        return diags
    return []


def _run_probe(kernel: str) -> ProbeResult:
    """Execute the probe now and cache a diagnosed ProbeResult."""
    from ..analysis.diagnostics import Diagnostic, record
    try:
        # Probe under x32.  The kernels trace their pallas_calls under
        # x32 (pallas_tiles._x32), but interpret-mode lowering
        # of the grid loop happens at *call* time, where the framework's
        # global x64 flag leaks i64 loop carries into the i32 kernel
        # body and StableHLO rejects the mixed compare.  x32 at call
        # time matches what the kernels actually compute.
        with jax.enable_x64(False):
            _PROBES[kernel]()
        result = ProbeResult(kernel, True)
        _logger.info("pallas kernel %s: probe compile OK", kernel)
    except Exception as exc:
        err = "".join(traceback.format_exception_only(type(exc), exc))
        err = err.strip()
        try:
            diags = _static_diagnose(kernel)
        except Exception:
            diags = []
        diags.append(Diagnostic(
            "TPU110",
            f"pallas kernel {kernel} failed its probe compile "
            f"({type(exc).__name__})",
            site=f"pallas_gate[{kernel}]",
            hint="probe_report() carries the full error; set "
                 "FLAGS_use_pallas_kernels=0 to run the XLA composites",
            data={"error": err[:2000]}))
        result = ProbeResult(kernel, False, error=err,
                             error_type=type(exc).__name__,
                             diagnostics=diags)
        for d in diags:
            record(d)
        _logger.warning("pallas kernel %s FAILED its probe compile "
                        "(%d diagnostic(s); see pallas_gate."
                        "probe_report())", kernel, len(diags))
    _probe_results[kernel] = result
    return result


_warned_partitioned = False


def _auto_partitioned() -> bool:
    """An active MeshPlan over several devices: the step compiles as
    one program that XLA's SPMD partitioner splits, and a Mosaic call
    cannot be split automatically (jax refuses to lower it: "Mosaic
    kernels cannot be automatically partitioned").  Such programs take
    the XLA composites, which the partitioner handles, until the
    kernels carry their own partitioning; said once, not silently."""
    global _warned_partitioned
    from ..distributed.auto_parallel.sharding import get_mesh_plan
    plan = get_mesh_plan()
    if plan is None or plan.size <= 1:
        return False
    if not _warned_partitioned:
        _warned_partitioned = True
        _logger.warning(
            "MeshPlan(%s) is active: programs partitioned by XLA use "
            "the XLA composites, not the Pallas kernels (a Mosaic call "
            "cannot be partitioned automatically)", plan.describe())
    return True


def pallas_enabled(kernel: str, manual: bool = False) -> bool:
    """True iff the named Pallas kernel should be used right now.
    Raises on a TPU when the kernel's probe compile fails.

    ``manual``: the call site sits inside a ``shard_map`` body, where
    each device runs the kernel on its own shard — legal under any
    mesh.  Other call sites are off while a MeshPlan is active (see
    ``_auto_partitioned``)."""
    if kernel not in _PROBES:
        raise ValueError(f"unknown pallas kernel {kernel!r}")
    if jax.default_backend() != "tpu":
        return False
    if not _flag_on():
        return False
    if not manual and _auto_partitioned():
        return False
    result = _probe_results.get(kernel)
    if result is None:
        result = _run_probe(kernel)
    if not result.ok:
        raise RuntimeError(
            f"pallas kernel {kernel} failed its probe compile on the "
            f"TPU: {result.error}\n"
            + "\n".join(f"  {d.code}: {d.message}"
                        for d in result.diagnostics)
            + "\nset FLAGS_use_pallas_kernels=0 to run the XLA "
              "composites instead")
    return True


def probe_kernel(kernel: str, force: bool = False) -> ProbeResult:
    """Probe one kernel and return the cached ProbeResult.

    With ``force=True`` the probe runs even off-TPU (interpret mode) —
    the CLI and tests use this to exercise the diagnosis path without
    hardware.  Without force, mirrors ``pallas_enabled`` gating.
    """
    if kernel not in _PROBES:
        raise ValueError(f"unknown pallas kernel {kernel!r}")
    if not force and (jax.default_backend() != "tpu" or not _flag_on()):
        return ProbeResult(kernel, False,
                           error="not probed (non-TPU backend or "
                                 "FLAGS_use_pallas_kernels off)",
                           error_type="skipped")
    result = _probe_results.get(kernel)
    if result is None:
        result = _run_probe(kernel)
    return result


def probe_report(kernel: str = None) -> dict:
    """Cached probe outcomes: {kernel: {ok, error, diagnostics, ...}}.

    Kernels never probed in this process report ``{"probed": False}``.
    Pass a kernel name for just that entry.
    """
    names = [kernel] if kernel else list(_PROBES)
    out = {}
    for name in names:
        if name not in _PROBES:
            raise ValueError(f"unknown pallas kernel {name!r}")
        res = _probe_results.get(name)
        out[name] = res.to_dict() if res else {"probed": False}
    return out[kernel] if kernel else out


def reset_probe_cache() -> None:
    _probe_results.clear()
