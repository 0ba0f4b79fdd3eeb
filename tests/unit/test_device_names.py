"""Names on the device's work (docs/OBSERVABILITY.md): every serving program
has a jit name of its own, and ``jax.named_scope`` marks the kernels, the
layer's halves, the MoE FFN, the optimizer and the ZeRO-3 waves. A scope is
trace-time only; what a device trace carries of it is the ``op_name`` of the
lowered program, so that is what these cases read: one parametrised test over
the lowered text of the decode step, both prefill passes, the train step and
the explicit ZeRO-3 step."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM


def _shapes(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        if hasattr(x, "shape") else x, tree)


class _Recorder:
    """Stands in for a jitted program, remembers the shapes of its first
    call and lowers the program for them on request."""

    def __init__(self, jitted):
        self.jitted, self.args, self.kwargs = jitted, None, None

    def __call__(self, *args, **kwargs):
        if self.args is None:
            self.args, self.kwargs = _shapes(args), _shapes(kwargs)
        return self.jitted(*args, **kwargs)

    def text(self) -> str:
        assert self.args is not None, "the program never ran"
        return self.jitted.lower(*self.args, **self.kwargs).as_text(
            debug_info=True)


def _serving_texts():
    cfg = LlamaConfig.tiny(vocab_size=128, max_position_embeddings=128)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    e = InferenceEngineV2(model=model, model_parameters=params, config={
        "dtype": jnp.float32,
        "state_manager": {"max_tracked_sequences": 4,
                          "max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": 32, "max_context": 128},
        "kv_cache": {"block_size": 16}})
    packed = e._pass_prefill = _Recorder(e._ensure_prefill_pass())
    paged = e._pass = e._pass_rungs[1] = _Recorder(e._pass)
    built = e._decode_step_prog
    steps = []

    def decode_step_prog(*a, **k):
        steps.append(_Recorder(built(*a, **k)))
        return steps[-1]

    e._decode_step_prog = decode_step_prog
    prompt = np.arange(1, 21, dtype=np.int32)
    e.put([1], [prompt[:12]])                 # from position 0: packed pass
    e.put([1], [prompt[12:]])                 # continues a context: paged pass
    e.decode_pipeline([1]).run(2)             # the fused decode step
    return {"decode_step": steps[0].text(), "prefill_packed": packed.text(),
            "paged_pass": paged.text()}


def _wide_head_text():
    """Heads 128 wide: the decode step takes the side-buffer schedule, whose
    K/V write comes once per program, after the layers."""
    cfg = LlamaConfig(vocab_size=128, hidden_size=256, intermediate_size=256,
                      num_hidden_layers=2, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=128,
                      dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    e = InferenceEngineV2(model=model, model_parameters=params, config={
        "dtype": jnp.float32,
        "state_manager": {"max_tracked_sequences": 2,
                          "max_ragged_sequence_count": 2,
                          "max_ragged_batch_size": 32, "max_context": 64},
        "kv_cache": {"block_size": 8}})
    built = e._decode_step_prog
    steps = []

    def decode_step_prog(*a, **k):
        steps.append(_Recorder(built(*a, **k)))
        return steps[-1]

    e._decode_step_prog = decode_step_prog
    e.put([1], [np.arange(1, 13, dtype=np.int32)])
    e.decode_pipeline([1]).run(2)
    return {"decode_step_d128": steps[0].text()}


def _train_texts():
    # steered in the test: on the CPU the model would take the dense
    # reference attention; the flash kernel (interpreted) is what a chip runs
    from deepspeed_tpu.ops import attention
    saved = attention._use_pallas, attention.FLASH_MIN_SEQ
    attention._use_pallas, attention.FLASH_MIN_SEQ = (lambda: True), 64
    try:
        return _train_texts_steered()
    finally:
        attention._use_pallas, attention.FLASH_MIN_SEQ = saved


def _train_texts_steered():
    cfg = LlamaConfig.tiny(vocab_size=128, max_position_embeddings=128)
    batch = {"input_ids": np.arange(8 * 128, dtype=np.int32).reshape(8, 128)
             % 128}
    engine, *_ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg),
        config={"train_batch_size": 8, "steps_per_print": 0,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3}, "mesh": {"fsdp": 8}},
        rngs=jax.random.PRNGKey(0))
    engine.train_batch(batch)
    step = engine._fused_step = _Recorder(engine._fused_step)
    engine.train_batch(batch)
    engine.eval_loss(batch)
    evalp = engine._eval_step = _Recorder(engine._eval_step)
    engine.eval_loss(batch)
    texts = {"train_step": step.text(), "eval": evalp.text()}
    engine.destroy()
    return texts


def _zero3_text():
    cfg = GPT2Config(vocab_size=128, n_positions=32, n_embd=64, n_layer=4,
                     n_head=4)
    model = GPT2LMHead(cfg)
    batch = {"input_ids": np.arange(8 * 32, dtype=np.int32).reshape(8, 32)
             % 128}
    params = model.init(jax.random.PRNGKey(0), batch)["params"]
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": 8, "steps_per_print": 0,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {
                    "stage": 3, "stage3_param_persistence_threshold": 0,
                    "stage3_prefetch_depth": 1,
                    "allgather_bucket_size": 100_000,
                    "reduce_bucket_size": 100_000},
                "mesh": {"fsdp": 8}})
    assert engine._zero3_plan is not None and engine._zero3_plan.n_waves == 4
    engine.train_batch(batch)
    step = engine._fused_step = _Recorder(engine._fused_step)
    engine.train_batch(batch)
    text = step.text()
    engine.destroy()
    return {"zero3_step": text}


def _moe_text():
    from deepspeed_tpu.inference.v2.ragged_model import _moe_ffn
    w = {"router": jnp.ones((64, 4)), "w_gate": jnp.ones((4, 64, 128)),
         "w_up": jnp.ones((4, 64, 128)), "w_down": jnp.ones((4, 128, 64))}

    def moe_layer(x, w):
        return _moe_ffn(x, w, 2, jnp.float32)

    out = {"moe": jax.jit(moe_layer).lower(jnp.ones((16, 64)), w).as_text(
        debug_info=True)}
    # afmoe's router (sigmoid scores, selection bias) and shared expert
    shared = {k: jnp.ones(v.shape[1:]) for k, v in w.items() if k != "router"}
    w2 = dict(w, expert_bias=jnp.zeros((4,)), shared=shared)

    def afmoe_layer(x, w):
        return _moe_ffn(x, w, 2, jnp.float32, routing={
            "score_func": "sigmoid", "route_norm": True, "route_scale": 2.0})

    out["afmoe_moe"] = jax.jit(afmoe_layer).lower(
        jnp.ones((16, 64)), w2).as_text(debug_info=True)
    return out


def _afmoe_text():
    """A model of mixed layer kinds: its ragged pass carries both kinds of
    attention scope and the gate."""
    from deepspeed_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM
    cfg = AfmoeConfig.tiny(dtype=jnp.float32)
    model = AfmoeForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    e = InferenceEngineV2(model=model, model_parameters=params, config={
        "dtype": "float32", "kv_cache": {"block_size": 8, "num_blocks": 16},
        "state_manager": {"max_context": 64, "max_tracked_sequences": 2,
                          "max_ragged_sequence_count": 2,
                          "max_ragged_batch_size": 2 + 16,
                          "prefill_chunk_size": 8}})
    paged = e._pass = e._pass_rungs[1] = _Recorder(e._pass)
    prompt = np.arange(1, 21, dtype=np.int32)
    e.put([1], [prompt[:12]])
    e.put([1], [prompt[12:]])
    return {"afmoe_paged_pass": paged.text()}


def _jamba_texts():
    """A model with Mamba layers: its passes carry the mixer's scopes, the
    chunked scan over prompt rows, and its decode step the one-token
    recurrence."""
    from deepspeed_tpu.models.jamba import JambaConfig, JambaForCausalLM
    cfg = JambaConfig.tiny(hidden_size=512, num_attention_heads=4,
                           mamba_dt_rank=16, dtype=jnp.float32)
    model = JambaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    e = InferenceEngineV2(model=model, model_parameters=params, config={
        "dtype": "float32", "kv_cache": {"block_size": 8, "num_blocks": 16},
        "state_manager": {"max_context": 64, "max_tracked_sequences": 2,
                          "max_ragged_sequence_count": 2,
                          "max_ragged_batch_size": 2 + 16,
                          "prefill_chunk_size": 8}})
    paged = e._pass = e._pass_rungs[1] = _Recorder(e._pass)
    built = e._decode_step_prog
    steps = []

    def decode_step_prog(*a, **k):
        steps.append(_Recorder(built(*a, **k)))
        return steps[-1]

    e._decode_step_prog = decode_step_prog
    prompt = np.arange(1, 21, dtype=np.int32)
    e.put([1], [prompt[:12]])
    e.put([1], [prompt[12:]])
    e.decode_pipeline([1]).run(2)
    return {"jamba_paged_pass": paged.text(),
            "jamba_decode_step": steps[0].text()}


@pytest.fixture(scope="module")
def texts():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    out = {}
    for build in (_serving_texts, _wide_head_text, _train_texts, _zero3_text,
                  _moe_text, _afmoe_text, _jamba_texts):
        out.update(build())
    return out


def _has_scope(text: str, scope: str) -> bool:
    """``scope`` as one component of some operation's name, bare or wrapped
    by a transformation (``jvp(flash_fwd)``)."""
    return re.search(r'loc\("(?:[^"]*[/(])?' + scope + r'(?:[/)][^"]*)?"',
                     text) is not None


CASES = [
    ("decode_step", "program", "jit_serve_decode_step"),
    ("decode_step", "scope", "attn"),
    ("decode_step", "scope", "ffn"),
    ("decode_step", "scope", "paged_decode_smalld"),   # the tiny head size's
    ("decode_step_d128", "scope", "paged_decode_sidebuf"),
    ("decode_step_d128", "scope", "kv_flush"),
    ("decode_step_d128", "scope", "kv_flush/paged_kv_row_write"),
    ("prefill_packed", "program", "jit_serve_prefill_packed"),
    ("prefill_packed", "scope", "flash_fwd_packed"),
    ("prefill_packed", "scope", "attn"),
    ("prefill_packed", "scope", "ffn"),
    ("paged_pass", "program", "jit_serve_paged_pass"),
    ("paged_pass", "scope", "paged_chunk"),
    ("paged_pass", "scope", "attn"),
    ("train_step", "program", "jit_step_fn"),
    ("train_step", "scope", "flash_fwd"),
    ("train_step", "scope", "flash_bwd_dkv"),   # the one fused backward call
    ("train_step", "scope", "optimizer"),
    ("eval", "program", "jit_train_eval_loss"),
    ("eval", "scope", "flash_fwd"),
    ("zero3_step", "program", "jit_step_fn"),
    ("zero3_step", "scope", "zero3/gather/w0"),
    ("zero3_step", "scope", "zero3/gather/w3"),
    ("zero3_step", "scope", "zero3/gather_bwd/w0"),
    ("zero3_step", "scope", "zero3/reduce_scatter/w0"),
    ("zero3_step", "scope", "zero3/reduce_scatter/w3"),
    ("zero3_step", "scope", "optimizer"),
    ("moe", "scope", "moe_ffn"),
    ("moe", "scope", "moe_ffn/router"),
    ("moe", "scope", "moe_ffn/sort"),
    ("moe", "scope", "moe_ffn/experts"),
    ("moe", "scope", "moe_ffn/combine"),
    ("afmoe_moe", "scope", "moe_ffn/router"),
    ("afmoe_moe", "scope", "moe_ffn/shared"),
    ("paged_pass", "scope", "attn/attn_full"),      # tiny llama: no window
    ("afmoe_paged_pass", "scope", "attn/attn_window"),
    ("afmoe_paged_pass", "scope", "attn/attn_full"),
    ("afmoe_paged_pass", "scope", "attn/gate"),
    ("afmoe_paged_pass", "scope", "moe_ffn/shared"),
    ("jamba_paged_pass", "scope", "ssm"),
    ("jamba_paged_pass", "scope", "ssm/in_proj"),
    ("jamba_paged_pass", "scope", "ssm/conv"),
    ("jamba_paged_pass", "scope", "ssm/scan"),
    ("jamba_paged_pass", "scope", "ssm/scan/ssm_chunk_scan"),
    ("jamba_paged_pass", "scope", "ssm/step"),      # a pass's decode rows
    ("jamba_paged_pass", "scope", "ssm/out_proj"),
    ("jamba_paged_pass", "scope", "attn/attn_full"),
    ("jamba_decode_step", "program", "jit_serve_decode_step"),
    ("jamba_decode_step", "scope", "ssm/step"),
    ("jamba_decode_step", "scope", "ssm/step/ssm_decode_step"),
    ("jamba_decode_step", "scope", "ssm/conv"),
    ("jamba_decode_step", "scope", "kv_flush"),
]


@pytest.mark.parametrize("program,kind,name", CASES,
                         ids=[f"{p}-{n}" for p, _, n in CASES])
def test_lowered_text_carries_the_name(texts, program, kind, name):
    text = texts[program]
    if kind == "program":
        assert re.search(r"module @" + name + r"\b", text), text[:200]
    else:
        assert _has_scope(text, name), f"no operation under {name!r}"


def test_no_serving_program_is_called_fwd(texts):
    for program in ("decode_step", "prefill_packed", "paged_pass"):
        assert "module @jit_fwd" not in texts[program]


def test_split_k_rung_is_part_of_the_name():
    from deepspeed_tpu.inference.v2 import engine_v2
    assert engine_v2._rung(1) == "" and engine_v2._rung(4) == "_sk4"
    f = engine_v2._program(lambda x: x + 1, "serve_paged_pass" + "_sk2")
    assert "module @jit_serve_paged_pass_sk2" in f.lower(jnp.ones(2)).as_text()


def test_state_build_program_is_named(eight_devices):
    seen = []
    real = jax.jit

    def spy(fn, *a, **k):
        seen.append(getattr(fn, "__name__", ""))
        return real(fn, *a, **k)

    cfg = LlamaConfig.tiny(vocab_size=128, max_position_embeddings=64)
    batch = {"input_ids": np.zeros((8, 16), np.int32)}
    engine, *_ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg),
        config={"train_batch_size": 8, "steps_per_print": 0,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "mesh": {"fsdp": 8}}, rngs=jax.random.PRNGKey(0))
    jax.jit = spy
    try:
        engine.eval_loss(batch)
    finally:
        jax.jit = real
    engine.destroy()
    assert "train_state_build_lazy" in seen and "train_eval_loss" in seen
