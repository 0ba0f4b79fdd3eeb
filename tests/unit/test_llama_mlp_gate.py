"""The MLP's gate (``models/llama.py::gated_activation``): ``act(gate) * up``
as a ``jax.custom_vjp`` whose rules hand ``act(gate) * up`` and ``(dgate,
dup)`` on as values of their own (an ``optimization_barrier`` each, unless
the engine says that the step sums its gradients across devices), so that the
TPU compiler does not re-form them, an ``exp`` and a divide an element,
inside the operand of each of the six products a layer that read them. Held
here: value and gradients against ``jax.grad`` of the plain expression, what
the rule keeps, that nothing changes where no gradient is asked, and the
model's loss and gradients against the plain expression's, sharded too. That
the barriers take in the compiled step is ``test_chip_compile.py``'s."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.models import llama
from deepspeed_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                        LlamaMLP, gated_activation)
from deepspeed_tpu.runtime import activation_checkpointing as ac

ACTS = {"silu": nn.silu, "gelu": nn.gelu}


def _plain(act, as_values, gate, up):
    return act(gate) * up


def _inputs(dtype, rows=24, width=96):
    rng = np.random.default_rng(rows)
    return tuple(jnp.asarray(2.0 * rng.standard_normal((rows, width),
                                                       np.float32), dtype)
                 for _ in range(3))


@pytest.mark.parametrize("act", sorted(ACTS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_value_and_both_gradients_are_the_plain_expressions(dtype, act):
    """float32 to 1e-6. bfloat16 to the bit: the rule forms ``dgate`` through
    ``jax.vjp`` of the same activation and ``dup`` as ``dh * act(gate)``, the
    operations autodiff runs on the plain expression, rounded where it
    rounds them."""
    gate, up, dh = _inputs(dtype)
    act = ACTS[act]

    def pulled(fn):
        out, vjp = jax.vjp(lambda g, u: fn(act, True, g, u), gate, up)
        return (out,) + vjp(dh)

    for got, want in zip(pulled(gated_activation), pulled(_plain)):
        assert got.dtype == dtype and got.shape == gate.shape
        if dtype == jnp.bfloat16:
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32))
        else:
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def _barriers(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("optimization_barrier")


@pytest.mark.parametrize("act", sorted(ACTS))
def test_no_gradient_asked_is_the_plain_expression(act):
    """Evaluation and the v1 engine's decode step (bound by bytes, and served
    by the fusion they have) see no barrier; a differentiated trace sees the
    forward rule's one and the backward rule's one, or neither where it is
    told not to (the same gradients)."""
    gate, up, _ = _inputs(jnp.bfloat16)
    act = ACTS[act]
    assert _barriers(lambda g, u: gated_activation(act, True, g, u),
                     gate, up) == 0
    np.testing.assert_array_equal(
        np.asarray(gated_activation(act, True, gate, up), np.float32),
        np.asarray(_plain(act, True, gate, up), np.float32))

    def grad(as_values):
        return jax.grad(lambda g, u: gated_activation(
            act, as_values, g, u).astype(jnp.float32).sum(), (0, 1))

    assert _barriers(grad(True), gate, up) == 2
    assert _barriers(grad(False), gate, up) == 0
    for got, want in zip(grad(False)(gate, up), grad(True)(gate, up)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))

    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    assert _barriers(lambda p: model.apply(
        {"params": p}, ids, method=model.forward_logits), params) == 0
    cache = llama.init_cache(cfg, 1, 16)
    assert _barriers(lambda p: model.apply(
        {"params": p}, ids[:, :1], cache, 0, method=model.decode),
        params) == 0
    # a step: two barriers a layer (and the loss head's two) — none of the
    # MLP's where the engine says that the step sums its gradients across
    # devices (no AdamW update rides in the products there, and the barriers
    # cost cell ``mistral7b-zero3x4.seq4k`` 0.7%)
    def step():         # a trace of its own each time, as an engine's is
        return jax.grad(
            lambda p: model.apply({"params": p}, {"input_ids": ids}))

    head = 2
    assert _barriers(step(), params) == head + 2 * cfg.num_hidden_layers
    with ac.keeping(None, grads_reduced=True):
        assert _barriers(step(), params) == head


@pytest.mark.parametrize("act", sorted(ACTS))
def test_the_rule_keeps_gate_and_up_and_nothing_else(act, capsys):
    """Under the "dots and attention" rung the MLP at Mistral-7B width keeps
    its two wide products' outputs, as the plain expression does (a layer's
    419,954,688 B of cell ``mistral7b-train.seq4k`` hold these 234,881,024):
    the backward re-forms ``act(gate) * up`` once, from them. The probe
    counts a value once where ``print_saved_residuals`` lists it again as
    the result of the jitted activation that hands its argument back."""
    cfg = LlamaConfig.mistral_7b(dtype=jnp.bfloat16, mlp_act=act)
    mlp = LlamaMLP(cfg)
    x = jax.ShapeDtypeStruct((1, 4096, cfg.hidden_size), jnp.bfloat16)
    variables = jax.eval_shape(mlp.init, jax.random.PRNGKey(0), x)
    policy = ac.LADDER[0][1]()
    wide = 4096 * cfg.intermediate_size * 2
    assert ac.kept_bytes(mlp.apply, policy, variables, x) == 2 * wide

    jax.ad_checkpoint.print_saved_residuals(
        jax.checkpoint(mlp.apply, policy=policy), variables, x)
    kept = [line for line in capsys.readouterr().out.splitlines()
            if "from the argument" not in line]
    assert len(kept) in (2, 3), kept
    assert all(line.startswith(f"bf16[1,4096,{cfg.intermediate_size}]")
               for line in kept), kept


def _tiny(plain, monkeypatch, **kw):
    """(loss and gradients of a tiny model, its parameters and batch); with
    ``plain`` the MLP runs the expression the rule replaced."""
    if plain:
        monkeypatch.setattr(llama, "gated_activation", _plain)
    model = LlamaForCausalLM(LlamaConfig.tiny(**kw))
    batch = {"input_ids": (np.arange(8 * 32, dtype=np.int32).reshape(8, 32)
                           * 7) % 256}
    params = model.init(jax.random.PRNGKey(0), batch)["params"]

    def grads(params, batch):
        return jax.value_and_grad(
            lambda p: model.apply({"params": p}, batch))(params)

    return grads, params, batch


@pytest.mark.parametrize("act", sorted(ACTS))
@pytest.mark.parametrize("remat", [False, True])
def test_the_models_loss_and_gradients_are_the_plain_expressions(
        remat, act, monkeypatch):
    """``LlamaForCausalLM`` at a tiny size, float32, checkpointed layers
    (rung 0, which keeps ``gate`` and ``up``) and not — and the same step
    with the batch's rows and every matrix's rows over eight devices, as the
    ZeRO-3 step holds them."""
    with monkeypatch.context() as m:
        grads, params, batch = _tiny(True, m, remat=remat, mlp_act=act)
        want = jax.jit(grads)(params, batch)
    grads, params, batch = _tiny(False, monkeypatch, remat=remat, mlp_act=act)
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("fsdp",))
    rows = NamedSharding(mesh, P("fsdp"))
    with ac.keeping(0 if remat else None):
        got = jax.jit(grads)(params, batch)
        sharded = jax.jit(grads, in_shardings=(rows, rows))(params, batch)
    for one in (got, sharded):
        for g, wnt in zip(jax.tree_util.tree_leaves(one),
                          jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(g, wnt, rtol=1e-5, atol=1e-6)
