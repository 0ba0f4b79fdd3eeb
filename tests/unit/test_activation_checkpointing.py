"""Activation checkpointing (remat) subsystem tests.

Parity model: reference ``tests/unit/runtime/activation_checkpointing`` — the
checkpointed forward/backward must produce bit-identical losses and grads vs the
un-checkpointed run (the reference compares against non-checkpointed autograd);
plus configure()/is_configured() API shape and policy selection.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.config import DeepSpeedTPUConfig
from deepspeed_tpu.runtime import activation_checkpointing as ac


@pytest.fixture(autouse=True)
def _reset_ac():
    yield
    ac.reset()


def _mlp_loss(params, x):
    h = x
    for w in params:
        h = jnp.tanh(h @ w)
    return jnp.sum(h ** 2)


def _params(key, n=3, d=16):
    keys = jax.random.split(key, n)
    return [jax.random.normal(k, (d, d)) / np.sqrt(d) for k in keys]


def test_checkpoint_matches_plain_grads():
    params = _params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))

    plain = jax.grad(_mlp_loss)(params, x)
    ckpt = jax.grad(lambda p, x: ac.checkpoint(_mlp_loss, p, x))(params, x)
    for a, b in zip(plain, ckpt):
        np.testing.assert_allclose(a, b, rtol=1e-6)


def test_checkpoint_with_selective_policy():
    ac.configure(partition_activations=True)
    assert ac.is_configured()
    params = _params(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 16))
    plain = jax.grad(_mlp_loss)(params, x)
    ckpt = jax.jit(jax.grad(lambda p, x: ac.checkpoint(_mlp_loss, p, x)))(params, x)
    for a, b in zip(plain, ckpt):
        np.testing.assert_allclose(a, b, rtol=1e-5)


def test_configure_from_config_dict():
    cfg = DeepSpeedTPUConfig.load({
        "train_batch_size": 8,
        "activation_checkpointing": {
            "partition_activations": True,
            "cpu_checkpointing": False,
            "number_checkpoints": 2,
        },
    })
    ac.configure(cfg)
    assert ac.is_configured()
    assert ac.current_policy() is not None
    # number_checkpoints=2 -> 8 layers partition into 2 chunks: only 2 boundary
    # activations stored (reference: num_checkpoints = activations stored)
    assert ac.layer_chunks(8) == [(0, 4), (4, 8)]


def test_layer_chunks_default_and_clamping():
    ac.configure()  # no number_checkpoints -> per-layer chunks
    assert ac.layer_chunks(3) == [(0, 1), (1, 2), (2, 3)]
    ac.configure(num_checkpoints=1)
    assert ac.layer_chunks(5) == [(0, 5)]  # whole net one recompute chunk
    ac.configure(num_checkpoints=99)
    assert ac.layer_chunks(4) == [(0, 1), (1, 2), (2, 3), (3, 4)]


def test_chunked_layers_grads_match_and_fewer_saved():
    import flax.linen as nn

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x):
            return jnp.tanh(nn.Dense(16)(x))

    class Net(nn.Module):
        remat: bool = True

        def setup(self):
            self.layers = [Layer(name=f"l{i}") for i in range(4)]

        def __call__(self, x):
            x = ac.apply_checkpointed_layers(
                self, x, lambda m, h, i: m.layers[i](h), 4, self.remat)
            return jnp.sum(x ** 2)

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16))
    params = Net(remat=False).init(jax.random.PRNGKey(1), x)
    g_plain = jax.grad(lambda p: Net(remat=False).apply(p, x))(params)
    ac.configure(num_checkpoints=2)
    g_chunk = jax.grad(lambda p: Net(remat=True).apply(p, x))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6), g_plain, g_chunk)


def test_policy_registry_and_errors():
    assert ac.resolve_policy(None) is None
    assert ac.resolve_policy("dots_saveable") is not None
    with pytest.raises(ValueError):
        ac.resolve_policy("not-a-policy")


def test_apply_remat_flax_module_grads_match():
    import flax.linen as nn

    class Block(nn.Module):
        @nn.compact
        def __call__(self, x):
            return jnp.tanh(nn.Dense(16)(x))

    class Net(nn.Module):
        remat: bool

        @nn.compact
        def __call__(self, x):
            cls = ac.apply_remat(Block, self.remat)
            for i in range(3):
                x = cls(name=f"b{i}")(x)
            return jnp.sum(x ** 2)

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16))
    plain_net, remat_net = Net(remat=False), Net(remat=True)
    params = plain_net.init(jax.random.PRNGKey(1), x)
    g1 = jax.grad(lambda p: plain_net.apply(p, x))(params)
    g2 = jax.grad(lambda p: remat_net.apply(p, x))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6), g1, g2)


def test_rng_tracker_fork_deterministic():
    tr = ac.RNGStatesTracker()
    tr.add("model-parallel-rng", 1234)
    with tr.fork() as k1:
        a = jax.random.normal(k1, (4,))
    with tr.fork() as k2:
        b = jax.random.normal(k2, (4,))
    assert not np.allclose(a, b)  # key advances
    tr2 = ac.RNGStatesTracker()
    tr2.add("model-parallel-rng", 1234)
    with tr2.fork() as k3:
        c = jax.random.normal(k3, (4,))
    np.testing.assert_allclose(a, c)  # same seed -> same stream
    with pytest.raises(ValueError):
        tr.add("model-parallel-rng", 0)


def test_model_parallel_seed_decorrelates_ranks():
    k0 = ac.model_parallel_seed(7, tp_rank=0)
    k1 = ac.model_parallel_seed(7, tp_rank=1)
    assert not np.array_equal(np.asarray(k0), np.asarray(k1))


def test_cpu_checkpointing_policy_selected():
    ac.configure(checkpoint_in_cpu=True)
    # offload policy object exists; on the CPU test platform we only check wiring,
    # execution of pinned_host offload is exercised on real TPU.
    assert ac.current_policy() is not None


# --------------------------------------------------------------------------- #
# what a checkpointed layer keeps when nobody names a policy (the ladder)
# --------------------------------------------------------------------------- #

GIB = 2 ** 30
#: bytes each rung keeps of one layer: cell-2-like proportions
KEPT = (400 << 20, 176 << 20, 96 << 20, 32 << 20)

#: (limit, resident, kept a rung, layers, other) -> rung. The margin is an
#: eighth of the limit: 16 GiB leave 14 GiB for everything.
CHOICES = {
    "rung0_fits": (16 * GIB, 9 * GIB, KEPT, 2, 2 * GIB, 0),
    "rung0_exactly": (16 * GIB, 14 * GIB - 800 * 2 ** 20, KEPT, 2, 0, 0),
    "rung1_more_layers": (16 * GIB, 9 * GIB, KEPT, 8, 2 * GIB, 1),
    "rung2": (16 * GIB, 9 * GIB, KEPT, 20, 2 * GIB, 2),
    "nothing_fits": (16 * GIB, 13 * GIB, KEPT, 8, 2 * GIB, 3),
    "state_alone_over_the_margin": (16 * GIB, int(15.2 * GIB), KEPT, 2, 0, 3),
    "no_limit": (0, 9 * GIB, KEPT, 2, 0, 3),
    "two_alive": (16 * GIB, 9 * GIB, KEPT, 4, 2 * GIB, 1),
}


@pytest.mark.parametrize("case", sorted(CHOICES))
def test_choose_rung_is_a_pure_function_of_what_it_is_given(case):
    limit, resident, kept, layers, other, want = CHOICES[case]
    alive = 2 if case == "two_alive" else 1
    got = [ac.choose_rung(limit, resident, kept, layers, alive=alive,
                          other=other) for _ in range(2)]
    assert got == [want, want]


def _offload_dots(policy):
    # the offload policies are closures made anew at every call: told by what
    # they say of a dot with no batch dimension (an Offloadable, not a bool)
    return not isinstance(policy(jax.lax.dot_general_p, dimension_numbers=(
        ((1,), (0,)), ((), ()))), bool)


#: who names what is kept, and the policy a walk then gets although an
#: engine is keeping rung 0 around the trace
NAMED = {
    "policy_named": (dict(), "dots_saveable",
                     lambda p: p is jax.checkpoint_policies.dots_saveable),
    "none_spells_full_recompute": (dict(), "none", lambda p: p is None),
    "partition_activations": (
        dict(partition_activations=True), None,
        lambda p: p is jax.checkpoint_policies.dots_with_no_batch_dims_saveable),
    "cpu_checkpointing": (dict(checkpoint_in_cpu=True), None, _offload_dots),
    "number_checkpoints": (dict(num_checkpoints=2), None, lambda p: p is None),
}


@pytest.mark.parametrize("case", sorted(NAMED))
def test_who_names_what_is_kept_wins_over_the_engine(case):
    configured, named, is_theirs = NAMED[case]
    if configured:
        ac.configure(**configured)
    with ac.keeping(0):
        assert is_theirs(ac.policy_for(named))
    assert is_theirs(ac.policy_for(named))


def test_nobody_names_a_policy():
    assert ac.policy_for(None) is None                  # no engine: as before
    ac.configure()                                      # an all-default block
    with ac.keeping(3):
        assert ac.policy_for(None) is None              # the last rung
    with ac.keeping(0):
        kept = ac.policy_for(None)
        dot = dict(dimension_numbers=(((1,), (0,)), ((), ())))
        assert kept(jax.lax.dot_general_p, **dot)
        assert ac.current_policy() is kept
    assert ac.policy_for(None) is None                  # put back
    with ac.keeping(None):
        assert ac.policy_for(None) is None


def test_narrow_dots_are_the_ones_no_wider_than_they_contract():
    dot = dict(dimension_numbers=(((2,), (0,)), ((), ())))
    x = jax.ShapeDtypeStruct((1, 8, 64), jnp.float32)

    def says(n_out):
        w = jax.ShapeDtypeStruct((64, n_out), jnp.float32)
        return ac.narrow_dots_saveable(jax.lax.dot_general_p, x, w, **dot)

    assert says(64) and says(16) and not says(128)
    batched = dict(dimension_numbers=(((2,), (1,)), ((0,), (0,))))
    assert not ac.narrow_dots_saveable(
        jax.lax.dot_general_p, x, jax.ShapeDtypeStruct((1, 64, 64), jnp.float32),
        **batched)
    assert not ac.narrow_dots_saveable(jax.lax.add_p, x, x)


def _tiny_llama(remat, **kw):
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig.tiny(remat=remat, **kw))


@pytest.fixture
def flash_on_the_cpu(monkeypatch):
    """The flash kernel (interpreted) for sequences the plain path would take."""
    from deepspeed_tpu.ops import attention
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(attention, "FLASH_MIN_SEQ", 16)


def _grads(model, params, batch):
    return jax.grad(lambda p: model.apply({"params": p}, batch))(params)


@pytest.mark.parametrize("path", ["plain", "flash"])
@pytest.mark.parametrize("rung", range(len(ac.LADDER)))
def test_every_rung_gives_the_gradients_of_no_checkpointing(rung, path,
                                                            request):
    """float32 on the CPU: a kept tensor is the tensor the recompute would
    have produced."""
    if path == "flash":
        request.getfixturevalue("flash_on_the_cpu")
    batch = {"input_ids": (np.arange(2 * 64, dtype=np.int32).reshape(2, 64)
                           * 7) % 256}
    plain_model, model = _tiny_llama(False), _tiny_llama(True)
    params = plain_model.init(jax.random.PRNGKey(0), batch)["params"]
    want = _grads(plain_model, params, batch)
    with ac.keeping(rung):
        got = _grads(model, params, batch)
        text = str(jax.make_jaxpr(
            lambda p: _grads(model, p, batch))(params))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6),
        want, got)
    if path == "flash":
        # per layer: forward and the one backward call — and the forward a
        # second time only where neither its output nor its log-sum-exp is
        # kept
        per_layer = 3 if rung == len(ac.LADDER) - 1 else 2
        assert text.count("pallas_call") == 2 * per_layer


def _listed_bytes(capsys, fn, *args) -> int:
    """What ``print_saved_residuals`` lists, less arguments and constants."""
    import re
    jax.ad_checkpoint.print_saved_residuals(fn, *args)
    total = 0
    for line in capsys.readouterr().out.splitlines():
        aval, _, where = line.partition(" ")
        # the last: the MLP's gate a second time — the jitted activation
        # that the backward re-runs hands its argument back, the value a
        # line above lists as a dot's; ``kept_bytes`` counts it once
        if where.startswith(("from the argument", "from a constant",
                             "from a literal",
                             "output of jitted function 'silu'")):
            continue
        dtype, shape = re.fullmatch(r"(\w+)\[([\d,]*)\]", aval).groups()
        dtype = {"f32": "float32", "bf16": "bfloat16", "i32": "int32",
                 "bool": "bool"}[dtype]
        total += int(np.prod([int(n) for n in shape.split(",") if n])) \
            * jnp.dtype(dtype).itemsize
    return total


@pytest.mark.parametrize("rung", range(len(ac.LADDER)))
def test_kept_bytes_are_what_print_saved_residuals_lists(rung, capsys,
                                                         flash_on_the_cpu):
    from deepspeed_tpu.models.llama import LlamaBlock, LlamaConfig
    cfg = LlamaConfig.tiny()
    block = LlamaBlock(cfg)
    x = jnp.ones((2, 64, cfg.hidden_size))
    positions = jnp.broadcast_to(jnp.arange(64)[None], (2, 64))
    variables = block.init(jax.random.PRNGKey(0), x, positions)

    def layer(variables, x):
        return block.apply(variables, x, positions)

    policy = ac.LADDER[rung][1]()
    listed = _listed_bytes(capsys, jax.checkpoint(layer, policy=policy),
                           variables, x)
    assert ac.kept_bytes(layer, policy, variables, x) == listed
    assert (listed == 0) == (rung == len(ac.LADDER) - 1)

    # and a model's walk reports the same of its first layer, plus the input
    model = _tiny_llama(True)
    batch = {"input_ids": np.zeros((2, 64), np.int32)}
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), batch)["params"])
    with ac.probing() as probe:
        jax.eval_shape(lambda p: model.apply({"params": p}, batch), params)
    assert probe.layers == cfg.num_hidden_layers
    assert probe.kept_per_layer()[rung] == listed + x.size * 4
    kept = probe.kept_per_layer()
    assert kept[0] > kept[1] > kept[2] > kept[3]


# --------------------------------------------------------------------------- #
# the engine's choice (a CPU device reports no limit: the tests give one)
# --------------------------------------------------------------------------- #

def _engine(limit, monkeypatch, zero=None, model_kw=None, **config):
    import deepspeed_tpu
    from deepspeed_tpu.accelerator import get_accelerator
    monkeypatch.setattr(type(get_accelerator()), "total_memory",
                        lambda self, device_index=None: limit)
    model = _tiny_llama(True, vocab_size=128, **(model_kw or {}))
    engine, *_ = deepspeed_tpu.initialize(
        model=model, rngs=jax.random.PRNGKey(0),
        config={"train_batch_size": 8, "steps_per_print": 0,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3, **(zero or {})},
                "mesh": {"fsdp": 8}, **config})
    batch = {"input_ids": np.arange(8 * 64, dtype=np.int32).reshape(8, 64)
             % 128}
    return engine, batch


def _step_text(engine, batch) -> str:
    """The fused step as ``train_batch`` would first build it, lowered."""
    from deepspeed_tpu.runtime.zero import prefetch
    staged = engine._prepare_batch(batch, 0).tree
    prefetch.configure(engine._zero3_plan)
    try:
        step = engine._make_fused_step(staged)
        return step.lower(engine.state, staged).as_text()
    finally:
        prefetch.configure(None)


def test_engine_keeps_what_fits_and_says_so(monkeypatch):
    from deepspeed_tpu.monitor.trace import tracer
    from deepspeed_tpu.utils.logging import logger
    lines = []
    monkeypatch.setattr(logger, "info", lambda msg, *a: lines.append(msg % a
                                                                     if a else msg))
    monkeypatch.setattr(logger, "log",
                        lambda level, msg, *a: lines.append(str(msg)))
    plain, batch = _engine(0, monkeypatch)
    want = [float(plain.train_batch(batch)) for _ in range(3)]
    assert plain.remat_plan is None
    plain.destroy()

    engine, _ = _engine(1 << 30, monkeypatch)
    got = [float(engine.train_batch(batch)) for _ in range(3)]
    plan = engine.remat_plan
    assert plan.rung == 0 and plan.limit_bytes == 1 << 30
    assert plan.layers == 2 and plan.kept_bytes == 2 * plan.kept_per_layer[0]
    # the rows of a micro-batch are spread over the eight devices
    assert plan.kept_per_layer[3] == 64 * 64 * 4
    assert plan.resident_bytes > 0 and plan.other_bytes > 0
    assert engine.compiles == 1                 # one program, compiled once
    np.testing.assert_allclose(got, want, rtol=1e-5)
    totals = tracer.totals
    assert totals["train/remat/rung"] == 0
    assert totals["train/remat/kept_bytes"] == plan.kept_bytes
    assert totals["train/remat/limit_bytes"] == 1 << 30
    assert totals["train/remat/resident_bytes"] == plan.resident_bytes
    assert totals["train/remat/step_bytes"] > plan.resident_bytes
    said = [l for l in lines if "activation checkpointing: rung 0" in l]
    assert len(said) == 1 and "dots and attention" in said[0]
    engine.destroy()


def test_guard_drops_a_rung_when_the_compiled_step_does_not_fit(monkeypatch):
    """The estimate admits rung 2 under this limit; the compiled step needs
    more than the limit at every rung, so the guard walks down to the last —
    what ran before there was a choice — and that one runs."""
    plain, batch = _engine(0, monkeypatch)
    want = float(plain.train_batch(batch))
    plain.destroy()
    engine, _ = _engine(500_000, monkeypatch)
    got = float(engine.train_batch(batch))
    assert engine.remat_plan.rung == len(ac.LADDER) - 1
    assert engine.compiles == 1
    np.testing.assert_allclose(got, want, rtol=1e-5)
    engine.destroy()


#: configurations that say what is kept themselves: the engine chooses
#: nothing, whatever the device's limit, and their step is the step it was
THEIRS = {
    "remat_policy": dict(model_kw={"remat_policy": "dots_saveable"}),
    "remat_policy_none": dict(model_kw={"remat_policy": "none"}),
    "partition_activations": dict(
        activation_checkpointing={"partition_activations": True}),
    "cpu_checkpointing": dict(
        activation_checkpointing={"cpu_checkpointing": True}),
    "number_checkpoints": dict(
        activation_checkpointing={"number_checkpoints": 1}),
    "stage3_prefetch_depth": dict(zero={
        "stage3_prefetch_depth": 1, "stage3_param_persistence_threshold": 0}),
}


@pytest.mark.parametrize("case", sorted(THEIRS))
def test_a_configuration_that_names_what_is_kept_keeps_its_program(
        case, monkeypatch):
    texts = []
    for limit in (0, 1 << 30):
        engine, batch = _engine(limit, monkeypatch, **THEIRS[case])
        engine._ensure_state(batch)
        assert (engine._zero3_plan is not None) == (
            case == "stage3_prefetch_depth")
        texts.append(_step_text(engine, batch))
        assert engine.remat_plan is None
        engine.destroy()
    assert texts[0] == texts[1]


def test_full_recompute_is_not_what_the_engine_compiles_with_room(monkeypatch):
    """The control of the test above: with nothing named and a limit, the
    step differs from the one a device without a limit gets."""
    texts = []
    for limit in (0, 1 << 30):
        engine, batch = _engine(limit, monkeypatch)
        engine._ensure_state(batch)
        texts.append(_step_text(engine, batch))
        engine.destroy()
    assert texts[0] != texts[1]


def _family(name):
    if name == "llama":
        return _tiny_llama(True), {}
    if name == "gpt2":
        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
        return GPT2LMHead(GPT2Config(vocab_size=128, n_positions=32, n_embd=64,
                                     n_layer=2, n_head=4, remat=True)), {}
    if name == "mixtral":
        from deepspeed_tpu.models.mixtral import (MixtralConfig,
                                                  MixtralForCausalLM)
        return MixtralForCausalLM(MixtralConfig.tiny(remat=True)), {}
    if name == "bert":
        from deepspeed_tpu.models.bert import BertConfig, BertForMaskedLM
        return BertForMaskedLM(BertConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=128,
            max_position_embeddings=32, remat=True)), \
            {"labels": np.zeros((2, 32), np.int32)}
    from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM
    return DecoderLM(DecoderConfig.tiny(remat=True)), {}


@pytest.mark.parametrize("family", ["llama", "gpt2", "mixtral", "bert",
                                    "decoder"])
def test_every_family_that_walks_checkpointed_layers_reports_to_the_probe(
        family):
    """One abstract trace, nothing compiled: each rung keeps no more of a
    layer than the one above it, and the last keeps the layer's input."""
    model, extra = _family(family)
    batch = {"input_ids": np.zeros((2, 32), np.int32), **extra}
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), batch)["params"])
    with ac.probing() as probe:
        jax.eval_shape(lambda p: model.apply({"params": p}, batch), params)
    kept = probe.kept_per_layer()
    assert probe.layers == 2
    assert kept[0] >= kept[1] >= kept[2] >= kept[3] > 0
    assert kept[0] > kept[3]
    # and a walk whose model names a policy asks for nothing
    if family == "llama":
        named = _tiny_llama(True, remat_policy="none")
        with ac.probing() as probe:
            jax.eval_shape(lambda p: named.apply({"params": p}, batch), params)
        assert probe.layers == 0
