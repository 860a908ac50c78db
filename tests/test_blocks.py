"""A model's blocks in a compiled step (ISSUE 37): ``observability.
block`` puts a closed vocabulary of scopes into the ``op_name`` metadata
of a step's instructions, the program keeps a map from every noted step
program's instructions to their block, and none of it changes the
device program.
"""
import contextlib
import gc
import glob
import json
import os
import re
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs, optimizer, static
from paddle_tpu.observability import blocks


@pytest.fixture(autouse=True)
def _gate_off_and_clean():
    prev = obs.enable(False)
    obs.get_timeline().clear()
    yield
    obs.get_timeline().clear()
    obs.enable(prev)


@contextlib.contextmanager
def blocks_stubbed_out():
    """``block`` and ``name_scope`` do nothing at all: no entry on the
    name stack, no ``jax.named_scope``.  JAX keeps the traces of the
    dispatcher's jitted ops, scopes and all: they are dropped on the way
    in and out."""
    enter, leave = blocks.name_scope.__enter__, blocks.name_scope.__exit__
    blocks.name_scope.__enter__ = lambda self: self
    blocks.name_scope.__exit__ = lambda self, *exc: False
    jax.clear_caches()
    try:
        yield
    finally:
        blocks.name_scope.__enter__ = enter
        blocks.name_scope.__exit__ = leave
        jax.clear_caches()


def instructions(text):
    """A module's instruction lines with the metadata stripped: what
    the device runs."""
    lines = [re.sub(r",? ?metadata=\{[^}]*\}", "", line)
             for line in text.splitlines() if " = " in line]
    assert lines
    return lines


def by_block(program, *opcodes):
    """``{block: count}`` of a map's instructions of these opcodes."""
    out = {}
    for entry in program["instructions"].values():
        if not opcodes or entry["opcode"] in opcodes:
            out[entry["block"]] = out.get(entry["block"], 0) + 1
    return out


# -- the scopes ------------------------------------------------------------
@jax.jit
def _norm(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)


def _two_blocks(x, w):
    with obs.block("attention"):
        h = jnp.tanh(_norm(x) @ w)
    with obs.block("ffn"):
        h = _norm(h) @ w.T
    return (h * h).sum() + x.sum()        # the sums: under no block


def _compiled_map(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return blocks.parse_hlo_blocks(compiled.as_text())


X, W = jnp.ones((8, 16)), jnp.full((16, 16), 0.1)


def test_scopes_survive_fusion_and_a_jitted_op_keeps_each_call_site():
    program = _compiled_map(_two_blocks, X, W)
    assert program["module"] == "jit__two_blocks"
    dots = by_block(program, "dot")
    assert dots == {"attention": 1, "ffn": 1}
    # _norm is one jitted function called under both blocks: its rsqrt
    # is attributed to each call site
    rsqrt = by_block(program, "rsqrt")
    assert rsqrt.get("attention") and rsqrt.get("ffn")
    # fusions carry a block too, and something is left under none
    fusions = by_block(program, "fusion")
    assert fusions.get("attention") and fusions.get("ffn")
    assert by_block(program).get("")


def test_forward_and_backward_fold_into_one_block():
    grad = jax.value_and_grad(_two_blocks, argnums=1)
    program = _compiled_map(grad, X, W)
    dots = by_block(program, "dot")
    # forward, and the backward's products by the cotangent
    assert dots["attention"] >= 2 and dots["ffn"] >= 2
    assert set(dots) <= {"attention", "ffn"}
    text = jax.jit(grad).lower(X, W).compile().as_text()
    assert "transpose(jvp(blk.ffn))" in text


def test_nested_blocks_take_the_innermost_and_xla_made_ones_their_source():
    hlo = "\n".join([
        "HloModule jit_engine_step, is_scheduled=true",
        "ENTRY %main (p: bf16[8,128]) -> bf16[8,128] {",
        '  %p = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)',
        '  %copy.3 = bf16[8,128]{0,1:T(8,128)(2,1)S(1)} copy(%p), '
        'metadata={op_name="jit(engine_step)/blk.attention/jit(f)/'
        'blk.kv_write/scatter" stack_frame_id=3}',
        '  %ragged_attention_fwd.7 = (bf16[8,128]{1,0:T(8,128)(2,1)}, '
        'f32[8]{0}) custom-call(%copy.3), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(engine_step)/'
        'blk.attention/cond/branch_1_fun/blk.attention/chunk/'
        'ragged_attention.fwd/pallas_call"}',
        '  %fusion.9 = bf16[8,128]{1,0} fusion(%copy.3), kind=kLoop, '
        'calls=%fused, metadata={op_name="jit(engine_step)/'
        'transpose(jvp(blk.experts))/blk.ffn/mul"}',
        # XLA's own: no op_name, the block of what they move
        '  %copy-start.2 = (bf16[8,128]{1,0}, bf16[8,128]{1,0:S(1)}, '
        'u32[]{:S(2)}) copy-start(%fusion.9)',
        '  %copy-done.2 = bf16[8,128]{1,0:S(1)} copy-done(%copy-start.2)',
        '  %copy.4 = bf16[8,128]{0,1} copy(%ragged_attention_fwd.7)',
        '  %copy.5 = bf16[8,128]{0,1} copy(%p)',
        '  ROOT %add.1 = bf16[8,128]{1,0} add(%copy-done.2, %p), '
        'metadata={op_name="jit(engine_step)/add"}',
        "}"])
    program = blocks.parse_hlo_blocks(hlo)
    assert program["module"] == "jit_engine_step"
    assert program["instructions"] == {
        "p": {"block": "", "opcode": "parameter"},
        "copy.3": {"block": "kv_write", "opcode": "copy"},
        # a kernel's own scope (_kernel_span) is no block
        "ragged_attention_fwd.7": {"block": "attention/chunk",
                                   "opcode": "custom-call"},
        "fusion.9": {"block": "ffn", "opcode": "fusion"},
        "copy-start.2": {"block": "ffn", "opcode": "copy-start"},
        "copy-done.2": {"block": "ffn", "opcode": "copy-done"},
        "copy.4": {"block": "attention/chunk", "opcode": "copy"},
        "copy.5": {"block": "", "opcode": "copy"},
        "add.1": {"block": "", "opcode": "add"}}


def test_what_xla_puts_into_a_branch_is_the_branchs_block():
    """A layout copy XLA makes inside the chunk's ``lax.cond`` body has
    the conditional's ``op_name`` cut short (block ``attention``) or
    none; JAX's instructions there all lie in ``attention/chunk``, so
    the copies are that block's too.  The other branch has no block of
    its own and keeps the caller's."""
    through = "jit(engine_step)/blk.attention/jit(pure_fwd)"
    chunk = through + "/cond/branch_1_fun/blk.attention/chunk"
    hlo = "\n".join([
        "HloModule jit_engine_step, is_scheduled=true",
        "",
        "%region_0.1 (arg.0: (bf16[8,128])) -> (bf16[8,128]) {",
        "  %arg.0 = (bf16[8,128]{1,0}) parameter(0), "
        f'metadata={{op_name="{through}"}}',
        "  %zeros.1 = bf16[8,128]{1,0} broadcast(%c), "
        f'metadata={{op_name="{through}/cond/branch_0_fun/broadcast"}}',
        "  %copy.9 = bf16[8,128]{0,1} copy(%zeros.1)",
        "  ROOT %tuple.1 = (bf16[8,128]{1,0}) tuple(%copy.9)",
        "}",
        "",
        "%region_1.2 (arg.1: (bf16[8,128])) -> (bf16[8,128]) {",
        "  %arg.1 = (bf16[8,128]{1,0}) parameter(0), "
        f'metadata={{op_name="{through}"}}',
        "  %gte.1 = bf16[8,128]{1,0} get-tuple-element(%arg.1), index=0",
        "  %copy.135 = bf16[8,128]{0,1} copy(%gte.1), "
        f'metadata={{op_name="{through}"}}',
        "  %copy.136 = bf16[8,128]{0,1} copy(%gte.1)",
        "  %slice.1 = bf16[8,128]{1,0} fusion(%gte.1), kind=kLoop, "
        f'calls=%fused, metadata={{op_name="{chunk}/slice"}}',
        "  %ragged_attention_fwd.8 = bf16[8,128]{1,0} custom-call("
        "%slice.1, %copy.135, %copy.136), custom_call_target="
        f'"tpu_custom_call", metadata={{op_name="{chunk}/'
        'ragged_attention.fwd/pallas_call"}',
        "  ROOT %tuple.2 = (bf16[8,128]{1,0}) tuple("
        "%ragged_attention_fwd.8)",
        "}",
        "",
        "ENTRY %main (p: bf16[8,128]) -> bf16[8,128] {",
        "  %p = bf16[8,128]{1,0} parameter(0)",
        "  %q.1 = bf16[8,128]{1,0} fusion(%p), kind=kLoop, calls=%f2, "
        f'metadata={{op_name="{through}/dot_general"}}',
        "  %tuple.3 = (bf16[8,128]{1,0}) tuple(%q.1)",
        "  %conditional.4 = (bf16[8,128]{1,0}) conditional(%pred, "
        "%tuple.3, %tuple.3), branch_computations={%region_0.1, "
        f'%region_1.2}}, metadata={{op_name="{through}/cond"}}',
        "  ROOT %out = bf16[8,128]{1,0} get-tuple-element("
        "%conditional.4), index=0",
        "}"])
    table = {name: entry["block"] for name, entry in
             blocks.parse_hlo_blocks(hlo)["instructions"].items()}
    assert table == {
        "arg.0": "attention", "zeros.1": "attention",
        "copy.9": "attention", "tuple.1": "attention",
        "arg.1": "attention/chunk", "gte.1": "attention/chunk",
        "copy.135": "attention/chunk", "copy.136": "attention/chunk",
        "slice.1": "attention/chunk",
        "ragged_attention_fwd.8": "attention/chunk",
        "tuple.2": "attention/chunk",
        "p": "", "q.1": "attention", "tuple.3": "attention",
        "conditional.4": "attention", "out": "attention"}


def test_block_takes_its_vocabulary_only():
    assert "attention/chunk" in obs.BLOCKS and "optimizer" in obs.BLOCKS
    with pytest.raises(ValueError, match="no block 'mlp'"):
        obs.block("mlp")


def test_block_outside_a_trace_enters_no_jax_scope(monkeypatch):
    entered = []
    monkeypatch.setattr(jax, "named_scope", lambda name: entered.append(
        name) or contextlib.nullcontext())
    with obs.block("ffn"):
        assert obs.scope_path() == "blk.ffn"
        with static.name_scope("inner"):
            assert obs.scope_path() == "blk.ffn/inner"
    assert obs.scope_path() == "" and not entered
    jax.make_jaxpr(lambda x: _two_blocks(x, W))(X)
    assert entered == ["blk.attention", "blk.ffn"]


# -- the static graph ------------------------------------------------------
def _tiny_static_step():
    """A two-layer BERT's training program, its Executor and a feed."""
    from paddle_tpu.models import BertConfig, BertForMaskedLM
    paddle.seed(3)
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        ids = static.data("ids", [2, 8], "int64")
        labels = static.data("labels", [2, 8], "int64")
        model = BertForMaskedLM(BertConfig(
            vocab_size=48, hidden_size=16, num_hidden_layers=2,
            num_attention_heads=2, intermediate_size=32,
            max_position_embeddings=8, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0))
        with static.name_scope("tower"):
            loss = model(ids, labels=labels)[0]
        optimizer.AdamW(learning_rate=1e-3,
                        parameters=model.parameters()).minimize(loss)
    rng = np.random.default_rng(0)
    feed = {"ids": rng.integers(0, 48, (2, 8)),
            "labels": rng.integers(0, 48, (2, 8))}
    return main, loss, feed


@pytest.fixture
def static_mode():
    paddle.enable_static()
    yield
    paddle.disable_static()


def _run_static(main, loss, feed):
    exe = static.Executor()
    value = float(exe.run(main, feed=feed, fetch_list=[loss])[0])
    (entry,) = exe._cache.values()
    return value, entry["compiled"].as_text()


def test_name_scope_and_block_reach_the_opdesc_and_the_walker(static_mode):
    main, loss, feed = _tiny_static_step()
    scopes = {op.scope for op in main.global_block().ops}
    assert scopes == {"tower/blk.embed", "tower/blk.attention",
                      "tower/blk.ffn", "tower/blk.head"}
    assert all(op.scope == src.scope for op, src in zip(
        main.clone().global_block().ops, main.global_block().ops))
    before = len(obs.program_blocks())
    _, text = _run_static(main, loss, feed)
    assert "tower/blk.attention" in text
    program = obs.program_blocks()[-1]
    assert len(obs.program_blocks()) == before + 1
    assert (program["label"], program["module"]) == ("exe:step",
                                                     "jit_exe_step")
    dots = by_block(program, "dot")
    assert set(dots) == {"attention", "ffn", "head"}
    # the update of every parameter is the optimizer's, and nothing of
    # the model's
    assert by_block(program, "fusion").get("optimizer")


def test_executor_step_is_the_same_program_without_blocks(static_mode):
    main, loss, feed = _tiny_static_step()
    with_blocks, text = _run_static(main, loss, feed)
    with blocks_stubbed_out():
        main, loss, feed = _tiny_static_step()
        assert {op.scope for op in main.global_block().ops} == {""}
        without, plain = _run_static(main, loss, feed)
    assert "blk." in text and "blk." not in plain
    assert instructions(text) == instructions(plain)
    assert with_blocks == without


# -- the serving engine ----------------------------------------------------
def _tiny_engine():
    from paddle_tpu.inference.serving import GenerationEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=64))
    model.eval()
    return GenerationEngine(model, num_blocks=64, max_batch=3,
                            max_model_len=64, prefill_chunk=16)


def _engine_step_text():
    eng = _tiny_engine()
    try:
        out = eng.generate([list(range(1, 12)), list(range(3, 8))],
                           max_new_tokens=4)
        (entry,) = eng._step_fn._cache.values()
        return out, entry["compiled"].as_text()
    finally:
        eng.close()


def test_engine_step_is_the_same_program_without_blocks():
    before = len(obs.program_blocks())
    tokens, text = _engine_step_text()
    program = obs.program_blocks()[-1]
    assert len(obs.program_blocks()) == before + 1
    assert (program["label"], program["module"]) == ("engine:step",
                                                     "jit_engine_step")
    found = set(by_block(program))
    assert {"embed", "attention", "attention/decode", "kv_write", "ffn",
            "head", "sampler", ""} <= found
    assert by_block(program, "scatter").get("kv_write")
    with blocks_stubbed_out():
        plain_tokens, plain = _engine_step_text()
    assert "blk." in text and "blk." not in plain
    assert instructions(text) == instructions(plain)
    assert tokens == plain_tokens


def test_engine_dispatch_says_what_the_step_carried():
    eng = _tiny_engine()
    try:
        eng.add_request(list(range(1, 30)), max_new_tokens=4)
        eng.add_request(list(range(3, 8)), max_new_tokens=6)
        obs.enable(True)
        for _ in range(4):
            eng.step()
    finally:
        eng.close()
    carried = [e.attrs for e in obs.get_timeline().events()
               if e.name == "engine:dispatch"]
    assert len(carried) == 4
    # 29 tokens in chunks of 16, then the 5-token prompt, then decode
    assert [(c["chunk_start"], c["chunk_tokens"]) for c in carried] == [
        (0, 16), (16, 13), (0, 5), (0, 0)]
    # each row attends to what it holds after the step's own tokens
    assert [c["context_tokens"] for c in carried] == [
        16, 29, 30 + 5, 31 + 6]
    assert [c["decode_rows"] for c in carried] == [0, 0, 1, 2]


# -- the map's keeping -----------------------------------------------------
class _Printable:
    def __init__(self, text):
        self._text = text

    def as_text(self):
        return self._text


def test_note_program_parses_once_and_keeps_no_executable():
    compiled = _Printable("HloModule jit_f\n\nENTRY %main () -> f32[] {\n"
                          "  ROOT %c = f32[] constant(1)\n}")
    gone = weakref.ref(compiled)
    obs.note_program("jit:f", compiled)
    del compiled
    gc.collect()
    assert gone() is None
    assert obs.program_blocks()[-1] == {
        "label": "jit:f", "module": "jit_f",
        "instructions": {"c": {"block": "", "opcode": "constant"}}}
    # a bounded record, and a program that cannot print itself is skipped
    for _ in range(blocks._KEEP + 3):
        obs.note_program("jit:g", _Printable("HloModule jit_g"))
    obs.note_program("jit:h", object())
    maps = obs.program_blocks()
    assert len(maps) == blocks._KEEP
    assert {m["label"] for m in maps} == {"jit:g"}


def test_to_static_notes_a_labelled_step_and_no_other_program():
    """Every ``to_static`` module is named after its function; a map is
    kept of the ones that say they are a step (``program_label``)."""
    def double_it(x):
        with obs.block("ffn"):
            return x * 2.0 + 1.0

    x = paddle.to_tensor(np.ones((4, 4), np.float32))
    before = obs.program_blocks()
    plain = paddle.jit.to_static(double_it)
    plain(x)
    (entry,) = plain._cache.values()
    assert entry["compiled"].as_text().startswith("HloModule jit_double_it")
    assert obs.program_blocks() == before
    step = paddle.jit.to_static(double_it)
    step.program_label = "tiny:step"
    step(x)
    program = obs.program_blocks()[-1]
    assert (program["label"], program["module"]) == ("tiny:step",
                                                     "jit_tiny_step")
    assert "ffn" in by_block(program)


def test_a_moved_scope_is_not_served_from_the_compile_cache(tmp_path,
                                                            monkeypatch):
    """JAX leaves metadata out of the persistent cache's key: two steps
    that differ in a block alone share one entry, and the later tree
    would read the older tree's map.  A step program (one whose map is
    kept) compiles with the metadata in its key; any other program
    keeps JAX's default and hits."""
    from jax.experimental.compilation_cache import compilation_cache as jcc
    from paddle_tpu.device import compile_cache as cc
    flag = "jax_compilation_cache_include_metadata_in_key"
    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    x = paddle.to_tensor(np.ones((4, 4), np.float32))

    def blocks_of(name, label):
        def tiny(x):
            with obs.block(name):
                return paddle.tanh(x) * 2.0
        fn = paddle.jit.to_static(tiny)
        fn.program_label = label
        fn(x)
        (entry,) = fn._cache.values()
        program = blocks.parse_hlo_blocks(entry["compiled"].as_text())
        return set(by_block(program)) - {""}

    monkeypatch.setattr(cc, "_applied", False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cc, "DEFAULT_CACHE_DIR", str(tmp_path / "cache"))
    jax.config.update("jax_enable_compilation_cache", True)
    jcc.reset_cache()
    try:
        assert blocks_of("ffn", "tiny:step") == {"ffn"}
        assert blocks_of("head", "tiny:step") == {"head"}
        assert obs.program_blocks()[-1]["label"] == "tiny:step"
        assert by_block(obs.program_blocks()[-1]).get("head")
        # what the default does to a program that keeps no map: the
        # older program comes back, scopes and all
        assert blocks_of("attention", None) == {"attention"}
        assert blocks_of("experts", None) == {"attention"}
        assert getattr(jax.config, flag) is False
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        jcc.reset_cache()


def test_profiler_writes_the_maps_beside_its_trace(tmp_path, monkeypatch):
    from paddle_tpu import profiler
    monkeypatch.setenv("PADDLE_TPU_PROFILE_DIR", str(tmp_path))
    obs.note_program("jit:f", _Printable("HloModule jit_f"))
    with profiler.Profiler():
        jnp.ones((4,)).block_until_ready()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "blocks.json"))
    assert glob.glob(os.path.join(os.path.dirname(path), "*.xplane.pb"))
    with open(path) as f:
        assert json.load(f)[-1]["module"] == "jit_f"
