"""SPMD sharding layer: partition rules -> PartitionSpec, MeshPlan.

This is the mesh-aware core that both execution tiers (the static
``Executor`` and ``jit.to_static``) compile against:

* ``match_partition_rules(rules, named_shapes)`` — fmengine-style regex
  matching of structural parameter names to ``PartitionSpec`` leaves.
  Scalar leaves are never sharded; a name matched by no rule raises.
* ``MeshPlan`` — the plan object.  Axes (``dp``/``tp``/``fsdp``/``pp``/
  ``ep``) come from a spec string such as ``"dp=4,tp=2"``
  (env: ``PADDLE_TPU_MESH``).
  It resolves rule hits into *legal* specs for a concrete shape (absent
  axes dropped, indivisible dims replicated), builds ``NamedSharding``s,
  and picks jit-with-NamedSharding vs ``shard_map`` per step function
  (``wrap_step``).
* ``annotate_params(layer)`` — stamps structural names from
  ``named_parameters()`` onto parameter tensors (``_spmd_name``) so the
  executor can match rules against real names instead of the
  auto-generated ``generated_tensor_N`` ids.
* ``shard_value`` / ``gather_value`` / ``make_shard_and_gather_fns`` —
  checkpoint save/load compatibility helpers.
* ``BERT_RULES`` / ``GPT_RULES`` — built-in rule sets for the bundled
  models (Megatron-style: column-parallel qkv/fc1, row-parallel
  out/fc2, fsdp over the remaining weight dim, embeddings over vocab).

The active plan is process-global: ``PADDLE_TPU_MESH`` selects one
lazily, ``set_mesh_plan`` overrides it programmatically.  Executable
caches key on ``plan_cache_token()`` so switching meshes never reuses a
stale executable.
"""
from __future__ import annotations

import math
import os
import re
import threading

import numpy as np

ENV_MESH = "PADDLE_TPU_MESH"

#: axes whose meaning is "replicas of the model" — the batch dimension
#: of feeds is sharded across these (fsdp shards params *and* batch).
DATA_AXES = ("dp", "fsdp")
MODEL_AXES = ("tp",)
#: stage axis: pipeline parallelism.  Not a sharding axis — partition
#: rules and batch specs never place tensors on it; it partitions the
#: *program* into stages (see auto_parallel.pipeline / stage_plan).
PIPELINE_AXES = ("pp",)
#: expert axis: MoE expert parallelism.  Stacked expert parameters
#: shard their leading [num_experts, ...] dim over it; token dispatch
#: crosses it with all-to-all (see distributed.moe).  Like tp it is a
#: model axis for batch purposes — feeds are never sharded over ep.
EXPERT_AXES = ("ep",)
KNOWN_AXES = DATA_AXES + MODEL_AXES + PIPELINE_AXES + EXPERT_AXES

__all__ = [
    "ENV_MESH", "DATA_AXES", "EXPERT_AXES", "MODEL_AXES", "KNOWN_AXES",
    "PIPELINE_AXES",
    "BERT_RULES", "GPT_RULES", "MOE_GPT_RULES", "MeshPlan",
    "annotate_params",
    "clear_mesh_plan", "gather_value", "gather_named", "get_mesh_plan",
    "make_shard_and_gather_fns", "match_partition_rules",
    "parse_mesh_spec", "plan_cache_token", "rules_for", "set_mesh_plan",
    "shard_value", "spmd_name",
]


def _pspec():
    from jax.sharding import PartitionSpec
    return PartitionSpec


def parse_mesh_spec(spec):
    """``"dp=4,tp=2"`` -> ``{"dp": 4, "tp": 2}`` (ordered, validated).
    ``;`` separates segments too (``"dp=4;pp=2"``) so the env knob
    composes with shell-quoted specs."""
    if isinstance(spec, dict):
        items = list(spec.items())
    else:
        items = []
        for part in str(spec).replace(";", ",").split(","):
            part = part.strip()
            if not part:
                continue
            if "=" not in part:
                raise ValueError(
                    f"bad mesh spec segment {part!r} in {spec!r}; "
                    f"expected axis=size, e.g. 'dp=4,tp=2'")
            name, _, size = part.partition("=")
            items.append((name.strip(), size.strip()))
    axes = {}
    for name, size in items:
        if name not in KNOWN_AXES:
            raise ValueError(
                f"unknown mesh axis {name!r}; known axes: {KNOWN_AXES}")
        if name in axes:
            raise ValueError(f"duplicate mesh axis {name!r} in {spec!r}")
        try:
            n = int(size)
        except (TypeError, ValueError):
            raise ValueError(
                f"mesh axis {name!r} has non-integer size {size!r}")
        if n < 1:
            raise ValueError(f"mesh axis {name!r} must be >= 1, got {n}")
        axes[name] = n
    if not axes:
        raise ValueError(f"empty mesh spec {spec!r}")
    return axes


def spmd_name(tensor):
    """Structural name for rule matching: ``_spmd_name`` if annotated
    (see :func:`annotate_params`), else the tensor's generated name."""
    return getattr(tensor, "_spmd_name", None) or getattr(
        tensor, "name", None) or ""


def annotate_params(layer, prefix=""):
    """Stamp structural names from ``named_parameters()`` onto the
    parameter tensors so partition rules can match them.

    Returns ``{structural_name: param}``.  Idempotent; safe to call on
    any ``nn.Layer`` before building the step program.
    """
    named = {}
    for name, p in layer.named_parameters():
        full = f"{prefix}{name}" if prefix else name
        try:
            p._spmd_name = full
        except AttributeError:
            pass
        named[full] = p
    return named


def _is_scalar_shape(shape):
    shape = tuple(shape)
    return len(shape) == 0 or math.prod(shape) <= 1


def match_partition_rules(rules, named_shapes):
    """Map structural names to raw ``PartitionSpec`` leaves via regex.

    ``rules`` is ``[(pattern, PartitionSpec)]``; the first pattern that
    ``re.search``-matches the name wins (fmengine semantics).  Scalar
    leaves (0-d, or a single element) are never sharded and skip
    matching entirely.  A non-scalar name matched by no rule raises
    ``ValueError`` — rule sets must be total (end with ``(".*", P())``
    to replicate everything else explicitly).

    ``named_shapes``: dict ``{name: shape}`` or iterable of
    ``(name, shape)``.  Returns ``{name: PartitionSpec}``.  The specs
    are the *raw* rule values; use ``MeshPlan.spec_for`` to legalise
    them against a concrete mesh and shape.
    """
    P = _pspec()
    if isinstance(named_shapes, dict):
        named_shapes = named_shapes.items()
    compiled = [(re.compile(pat), spec) for pat, spec in rules]
    out = {}
    for name, shape in named_shapes:
        if _is_scalar_shape(shape):
            out[name] = P()
            continue
        for pat, spec in compiled:
            if pat.search(name):
                out[name] = spec
                break
        else:
            raise ValueError(
                f"Partition rule not found for param: {name!r} "
                f"(shape {tuple(shape)}); add a rule or a catch-all "
                f"('.*', PartitionSpec())")
    return out


# ---------------------------------------------------------------------------
# Built-in rule sets for the bundled models.
#
# Weight layout note: ``nn.Linear`` stores weight as [in, out], so
# "column parallel" (split the output features) shards dim 1 over tp
# and "row parallel" (split the input features) shards dim 0 over tp.
# fsdp takes whichever weight dim tp does not.  On a mesh without an
# axis named in a spec, MeshPlan.spec_for drops that axis, so one rule
# set serves dp-only, tp-only, fsdp-only and mixed meshes.
# ---------------------------------------------------------------------------

def _P(*args):
    return _pspec()(*args)


def BERT_RULES():
    """Partition rules for the bundled BERT models (structural names
    like ``bert.encoder.0.attention.qkv.weight``)."""
    return [
        (r"word_embeddings\.weight$", _P("tp", "fsdp")),
        (r"(position|token_type)_embeddings\.weight$", _P(None, "fsdp")),
        (r"attention\.qkv\.weight$", _P("fsdp", "tp")),
        (r"attention\.qkv\.bias$", _P("tp")),
        (r"attention\.out\.weight$", _P("tp", "fsdp")),
        (r"fc1\.weight$", _P("fsdp", "tp")),
        (r"fc1\.bias$", _P("tp")),
        (r"fc2\.weight$", _P("tp", "fsdp")),
        (r"cls\.transform\.weight$", _P("fsdp", None)),
        (r"pooler\.dense\.weight$", _P("fsdp", None)),
        (r"(ln|ln1|ln2|layer_norm)\.(weight|bias)$", _P()),
        (r"bias$", _P()),
        (r".*", _P()),
    ]


def GPT_RULES():
    """Partition rules for the bundled GPT models (structural names
    like ``gpt.h.0.attn.qkv_proj.weight``)."""
    return [
        (r"wte\.weight$", _P("tp", "fsdp")),
        (r"wpe\.weight$", _P(None, "fsdp")),
        (r"attn\.qkv_proj\.weight$", _P("fsdp", "tp")),
        (r"attn\.qkv_proj\.bias$", _P("tp")),
        (r"attn\.out_proj\.weight$", _P("tp", "fsdp")),
        (r"mlp\.fc1\.weight$", _P("fsdp", "tp")),
        (r"mlp\.fc1\.bias$", _P("tp")),
        (r"mlp\.fc2\.weight$", _P("tp", "fsdp")),
        (r"lm_head\.weight$", _P("fsdp", "tp")),
        (r"(ln_1|ln_2|ln_f|ln)\.(weight|bias)$", _P()),
        (r"bias$", _P()),
        (r".*", _P()),
    ]


def MOE_GPT_RULES():
    """Partition rules for the bundled MoE GPT (``models/moe_gpt.py``):
    the stacked expert weights [E, ...] shard their expert dim over
    ``ep`` (dropped automatically on meshes without one); the router
    stays replicated so every device ranks every expert; the shared
    trunk follows ``GPT_RULES``."""
    return [
        (r"mlp\.router$", _P()),
        (r"mlp\.w[12]$", _P("ep", None, None)),
        (r"mlp\.b[12]$", _P("ep", None)),
    ] + GPT_RULES()


_BUILTIN_RULES = {"bert": BERT_RULES, "gpt": GPT_RULES,
                  "moe_gpt": MOE_GPT_RULES}


def rules_for(model):
    """Built-in rule set by model family name ('bert' or 'gpt')."""
    try:
        return _BUILTIN_RULES[model.lower()]()
    except KeyError:
        raise ValueError(
            f"no built-in partition rules for {model!r}; "
            f"known: {sorted(_BUILTIN_RULES)}")


class MeshPlan:
    """A named device mesh + partition rules = how a step function is
    compiled and laid out.

    ``spec``: mesh axes, e.g. ``"dp=4,tp=2"`` (string or dict).
    ``rules``: ``[(regex, PartitionSpec)]`` partition rules for named
    parameters; empty/None means every parameter is replicated (pure
    data parallelism).
    ``virtual=True`` builds a plan without a jax ``Mesh`` — rule
    resolution and per-device byte math still work (used by tpu_lint on
    single-device hosts), but anything needing real devices raises.
    """

    def __init__(self, spec, rules=None, devices=None, virtual=False):
        self.axis_sizes = parse_mesh_spec(spec)
        self.axis_names = tuple(self.axis_sizes)
        self.rules = list(rules) if rules else []
        self.size = math.prod(self.axis_sizes.values())
        self._mesh = None
        self._virtual = bool(virtual)
        # Bumped by shrink(): keeps executable-cache keys fresh across a
        # recovery even when the shrunk topology coincides with an old one.
        self._generation = 0
        self.shrink_findings = []
        if not virtual:
            import jax
            devs = list(devices) if devices is not None else jax.devices()
            if self.size > len(devs):
                raise ValueError(
                    f"mesh {self.describe()!r} needs {self.size} devices "
                    f"but only {len(devs)} are visible; set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count=N for a "
                    f"host mesh, or shrink {ENV_MESH}")
            from jax.sharding import Mesh
            arr = np.asarray(devs[: self.size]).reshape(
                tuple(self.axis_sizes.values()))
            self._mesh = Mesh(arr, self.axis_names)

    # -- identity ---------------------------------------------------------
    @property
    def mesh(self):
        if self._mesh is None:
            raise RuntimeError(
                f"MeshPlan({self.describe()!r}) is virtual (no devices); "
                "rebuild with virtual=False on a host with enough devices")
        return self._mesh

    @property
    def is_virtual(self):
        return self._virtual

    def axis_size(self, name):
        return self.axis_sizes.get(name, 1)

    def describe(self):
        return ",".join(f"{k}={v}" for k, v in self.axis_sizes.items())

    def rules_token(self):
        return tuple((pat, str(spec)) for pat, spec in self.rules)

    def cache_token(self):
        """Hashable token identifying mesh topology + rule set + the
        configured collective-overlap mode; mixed into executable-cache
        keys so plans never share executables.  The pp axis enters via
        ``axis_sizes``; the overlap mode via ``overlap.mode_token()``."""
        from . import overlap as _overlap
        return (tuple(self.axis_sizes.items()), self.rules_token(),
                _overlap.mode_token(), self._generation)

    def __repr__(self):
        return (f"MeshPlan({self.describe()}, rules={len(self.rules)}"
                f"{', virtual' if self._virtual else ''})")

    # -- pipeline stages --------------------------------------------------
    @property
    def num_stages(self):
        """Pipeline depth: size of the ``pp`` axis (1 = no pipeline)."""
        return self.axis_sizes.get("pp", 1)

    def stage_plan(self, stage):
        """The sub-plan one pipeline stage computes under.

        Slices this plan's device array along the ``pp`` axis and
        rebuilds a MeshPlan over the remaining axes (same rules), so a
        stage's step function compiles and shards exactly like a
        non-pipelined program on its device subset.  Returns ``None``
        when nothing but ``pp`` (or nothing at all) remains — the stage
        runs as a plain jitted function on its slice's first device.
        """
        stages = self.num_stages
        if not 0 <= stage < stages:
            raise ValueError(f"stage {stage} out of range for "
                             f"pp={stages}")
        rest = {a: n for a, n in self.axis_sizes.items()
                if a != "pp" and n > 1}
        if "pp" not in self.axis_sizes:
            return self if stage == 0 else None
        if self._virtual:
            return MeshPlan(rest, rules=self.rules, virtual=True) \
                if rest else None
        arr = np.asarray(self.mesh.devices)
        idx = self.axis_names.index("pp")
        devs = list(np.take(arr, [stage], axis=idx).ravel())
        if not rest:
            return None
        return MeshPlan(rest, rules=self.rules, devices=devs)

    def stage_devices(self, stage):
        """Devices backing one pipeline stage (row of the pp axis)."""
        arr = np.asarray(self.mesh.devices)
        if "pp" not in self.axis_sizes:
            return list(arr.ravel())
        idx = self.axis_names.index("pp")
        return list(np.take(arr, [stage], axis=idx).ravel())

    # -- elastic recovery -------------------------------------------------
    def shrink(self, surviving_devices):
        """Rebuild this plan over a smaller device set after a loss.

        dp is the preferred shrink axis: it drops to the largest divisor
        of the original dp size that still fits, so global-batch
        divisibility (and therefore bit-identical resume on the shrunk
        mesh) is preserved.  Model-parallel axes that no longer fit
        (tp, then fsdp, then pp, then ep — ep=1 keeps every expert
        resident on every device) fall back to replication — each drop is
        recorded as a TPU505 finding on ``shrink_findings`` and in the
        diagnostic log.  The new plan reuses the SAME partition rules,
        so ``_legalize`` re-materializes specs on the smaller mesh, and
        carries a bumped ``_generation`` so ``cache_token()`` never
        collides with a pre-loss executable cache entry.
        """
        from ...analysis import diagnostics as _diag
        if self._virtual:
            raise RuntimeError("cannot shrink a virtual MeshPlan")
        devs = list(surviving_devices)
        if not devs:
            raise ValueError("shrink() needs at least one surviving device")
        axes = dict(self.axis_sizes)
        findings = []

        def _non_dp():
            return math.prod(v for k, v in axes.items() if k != "dp")

        for ax in ("tp", "fsdp", "pp", "ep"):
            if _non_dp() <= len(devs):
                break
            if axes.get(ax, 1) > 1:
                msg = (f"mesh shrink {self.describe()} -> {len(devs)} "
                       f"devices: axis {ax}={axes[ax]} no longer fits; "
                       f"its parameters fall back to replication")
                findings.append(_diag.record(_diag.Diagnostic(
                    "TPU505", msg, site=f"mesh.shrink.{ax}",
                    hint="restore capacity or re-launch with a smaller "
                         f"{ax} degree to re-shard these parameters",
                    data={"axis": ax, "old_size": axes[ax],
                          "surviving": len(devs)})))
                axes[ax] = 1
        if _non_dp() > len(devs):
            raise ValueError(
                f"cannot shrink {self.describe()} onto {len(devs)} "
                f"devices: model-parallel axes need {_non_dp()}")
        old_dp = axes.get("dp", 1)
        cap = len(devs) // _non_dp()
        new_dp = max(d for d in range(1, old_dp + 1)
                     if old_dp % d == 0 and d <= cap)
        if "dp" in axes:
            axes["dp"] = new_dp
        new = MeshPlan(axes, rules=self.rules, devices=devs)
        new._generation = self._generation + 1
        new.shrink_findings = findings
        return new

    # -- spec resolution --------------------------------------------------
    def data_axes(self):
        """Mesh axes the feed batch dimension is sharded over."""
        return tuple(a for a in DATA_AXES
                     if self.axis_sizes.get(a, 1) > 1)

    def data_parallel_size(self):
        return math.prod(self.axis_sizes.get(a, 1) for a in DATA_AXES)

    def _legalize(self, raw_spec, shape):
        """Clamp a raw rule spec to a concrete shape on this mesh:
        absent/size-1 axes dropped, indivisible dims replicated, an
        axis used at most once across the spec."""
        P = _pspec()
        shape = tuple(shape)
        if _is_scalar_shape(shape):
            return P()
        entries = tuple(raw_spec)[: len(shape)]
        used, out = set(), []
        for dim, entry in zip(shape, tuple(entries) + (None,) * len(shape)):
            if entry is None:
                out.append(None)
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            names = tuple(n for n in names
                          if self.axis_sizes.get(n, 1) > 1 and n not in used)
            factor = math.prod(self.axis_sizes[n] for n in names)
            if factor <= 1 or dim % factor != 0:
                out.append(None)
                continue
            used.update(names)
            out.append(names if len(names) > 1 else names[0])
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def match(self, name, shape):
        """Lenient rule lookup: ``(matched, legal_spec)``.

        Scalars are always ``(True, P())``.  With no rules, everything
        is ``(True, P())`` (replicated — pure DP).  A rule miss returns
        ``(False, P())`` instead of raising so the executor can shard
        what it knows and lint the rest (TPU501).
        """
        P = _pspec()
        shape = tuple(shape)
        if _is_scalar_shape(shape) or not self.rules:
            return True, P()
        for pat, spec in self._compiled_rules():
            if pat.search(name):
                return True, self._legalize(spec, shape)
        return False, P()

    def _compiled_rules(self):
        cached = getattr(self, "_rules_compiled", None)
        if cached is None:
            cached = [(re.compile(pat), spec) for pat, spec in self.rules]
            self._rules_compiled = cached
        return cached

    def spec_for(self, name, shape):
        return self.match(name, shape)[1]

    def specs_for(self, named_shapes):
        if isinstance(named_shapes, dict):
            named_shapes = named_shapes.items()
        return {name: self.spec_for(name, shape)
                for name, shape in named_shapes}

    def batch_spec(self, shape):
        """Spec for a feed/activation: dim 0 sharded over the data
        axes when divisible, otherwise fully replicated."""
        P = _pspec()
        shape = tuple(shape)
        axes = self.data_axes()
        if not axes or not shape or _is_scalar_shape(shape):
            return P()
        factor = math.prod(self.axis_sizes[a] for a in axes)
        if shape[0] % factor != 0:
            return P()
        return P(axes if len(axes) > 1 else axes[0])

    # -- shardings --------------------------------------------------------
    def sharding(self, spec=None):
        from jax.sharding import NamedSharding
        return NamedSharding(self.mesh, spec if spec is not None
                             else _pspec()())

    def replicated(self):
        return self.sharding(_pspec()())

    def tree_shardings(self, spec_tree):
        """Map a pytree of PartitionSpec leaves to NamedShardings."""
        import jax
        P = _pspec()
        return jax.tree_util.tree_map(
            lambda s: self.sharding(s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))

    # -- per-device memory math ------------------------------------------
    def shard_factor(self, spec):
        """How many ways a spec splits a buffer across the mesh."""
        if spec is None:
            return 1
        factor = 1
        for entry in tuple(spec):
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            for n in names:
                factor *= self.axis_sizes.get(n, 1)
        return max(1, factor)

    def per_device_nbytes(self, nbytes, spec):
        """Bytes one device holds for a buffer laid out as ``spec``:
        sharded residents divide by the axis-size product, replicated
        buffers are charged whole."""
        return int(nbytes) // self.shard_factor(spec)

    # -- step-function compilation ---------------------------------------
    def wrap_step(self, fn, in_shardings=None, out_shardings=None,
                  in_specs=None, out_specs=None, donate_argnums=(),
                  static_argnums=(), **jit_kwargs):
        """Compile a step function for this mesh.

        Two modes (Titanax semantics — explicit shardings mean GSPMD,
        map-style specs mean per-shard SPMD):

        * ``in_shardings``/``out_shardings`` given (pytrees of
          ``PartitionSpec`` or ``NamedSharding``): ``jax.jit`` with
          NamedShardings — the partitioner inserts collectives.
        * ``in_specs``/``out_specs`` given: ``shard_map`` over the
          mesh — ``fn`` sees per-shard arrays and writes its own
          collectives (``jax.lax.p*`` over the axis names).
        * neither: plain ``jax.jit`` under this mesh's context so
          ``with_sharding_constraint`` inside ``fn`` resolves.
        """
        import jax
        from jax.sharding import NamedSharding
        P = _pspec()
        if in_specs is not None or out_specs is not None:
            if in_shardings is not None or out_shardings is not None:
                raise ValueError(
                    "pass either in_/out_shardings (jit) or "
                    "in_/out_specs (shard_map), not both")
            mapped = jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                                   out_specs=out_specs)
            return jax.jit(mapped, donate_argnums=donate_argnums,
                           static_argnums=static_argnums, **jit_kwargs)
        is_leaf = lambda x: isinstance(x, (P, NamedSharding))  # noqa: E731
        to_ns = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda s: s if isinstance(s, NamedSharding) else self.sharding(s),
            t, is_leaf=is_leaf)
        if in_shardings is not None:
            jit_kwargs["in_shardings"] = to_ns(in_shardings)
        if out_shardings is not None:
            jit_kwargs["out_shardings"] = to_ns(out_shardings)
        return jax.jit(fn, donate_argnums=donate_argnums,
                       static_argnums=static_argnums, **jit_kwargs)

    # -- placement --------------------------------------------------------
    def place(self, value, spec):
        """``device_put`` a host or device array under ``spec``."""
        import jax
        return jax.device_put(value, self.sharding(spec))


# ---------------------------------------------------------------------------
# Checkpoint shard/gather helpers
# ---------------------------------------------------------------------------

def shard_value(value, plan, spec):
    """Place a (host) value onto the plan's mesh under ``spec``."""
    return plan.place(value, spec)


def gather_value(value):
    """Full host ``np.ndarray`` from a (possibly sharded) jax array.

    Works for any fully-addressable array — single-controller meshes
    (the only kind this repo builds) always are.
    """
    try:
        return np.asarray(value)
    except Exception:
        import jax
        gathered = jax.device_get(value)
        return np.asarray(gathered)


def gather_named(named_tensors):
    """``{name: tensor}`` (or ``[(name, tensor)]``) -> ``{name: np}``,
    gathering every shard to the host — checkpoint-save compatible."""
    if isinstance(named_tensors, dict):
        named_tensors = named_tensors.items()
    out = {}
    for name, t in named_tensors:
        val = getattr(t, "_value", t)
        out[name] = gather_value(val)
    return out


def make_shard_and_gather_fns(plan, named_shapes):
    """fmengine-style helper: per-name ``shard_fn(host_array)`` /
    ``gather_fn(device_array)`` pairs for checkpoint save/load."""
    specs = plan.specs_for(named_shapes)

    def _shard_fn(spec):
        return lambda x: plan.place(x, spec)

    shard_fns = {name: _shard_fn(spec) for name, spec in specs.items()}
    gather_fns = {name: gather_value for name in specs}
    return shard_fns, gather_fns


# ---------------------------------------------------------------------------
# Process-global active plan
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_override = None          # plan set programmatically (or explicit None)
_override_set = False
_env_cache = {}           # env string -> MeshPlan


def _rehome_rng_state():
    """A step compiled under a plan hands the default generator's state
    back replicated over the plan's mesh.  Left there, every later
    eager random op — parameter init — is born committed to that mesh,
    and a program compiled without the plan refuses such arguments.
    When the plan changes, the state goes back to an uncommitted copy
    (the next plan's first dispatch places it again)."""
    import jax
    from ...framework.random import default_generator
    state = default_generator().state_tensor
    value = state._value
    if isinstance(value, jax.Array) and len(value.sharding.device_set) > 1:
        state._value = jax.numpy.asarray(np.asarray(value))


def set_mesh_plan(plan):
    """Set (or with ``None`` clear back to env-driven) the active plan."""
    global _override, _override_set
    with _lock:
        _override = plan
        _override_set = plan is not None
    _rehome_rng_state()


def clear_mesh_plan():
    global _override, _override_set
    with _lock:
        _override = None
        _override_set = False
        _env_cache.clear()
    _rehome_rng_state()


def get_mesh_plan():
    """Active :class:`MeshPlan`, or ``None`` when unsharded.

    Programmatic ``set_mesh_plan`` wins; otherwise ``PADDLE_TPU_MESH``
    (e.g. ``dp=4,tp=2``) lazily builds one over the visible devices.
    A mesh of total size 1 means "not sharded" and yields ``None``.
    """
    with _lock:
        if _override_set:
            return _override
    env = os.environ.get(ENV_MESH, "").strip()
    if not env:
        return None
    with _lock:
        plan = _env_cache.get(env)
        if plan is None:
            plan = MeshPlan(env)
            _env_cache[env] = plan
    return plan if plan.size > 1 else None


def plan_cache_token():
    """Token for executable-cache keys: ``None`` when unsharded."""
    plan = get_mesh_plan()
    return None if plan is None else plan.cache_token()
