"""The training loss head (``models/llama.py::chunked_causal_lm_loss``): a
``jax.custom_vjp`` that forms the gradient with respect to each chunk's
logits in the iteration that forms the logits. The chip's check covers the
forward pass only, so value AND gradients are held here against ``jax.grad``
of the plain ``causal_lm_loss(x @ w)``, and the compiled program is read for
what may not be there: a fourth product of the head's shape, a residual of
``V`` columns."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.models.llama import causal_lm_loss, chunked_causal_lm_loss
from deepspeed_tpu.monitor.trace import tracer

T, C, V = 9, 16, 40


def _inputs(dtype, B, transpose, bias):
    rng = np.random.default_rng(B)
    x = jnp.asarray(rng.standard_normal((B, T, C), np.float32), dtype)
    w = jnp.asarray(0.3 * rng.standard_normal(
        (C, V) if transpose else (V, C), np.float32), dtype)
    b = jnp.asarray(rng.standard_normal(V, np.float32)) if bias else None
    y = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
    return x, w, b, y


def _plain(x, w, b, y, transpose):
    w = w if transpose else w.T
    logits = x.astype(jnp.float32) @ w.astype(jnp.float32)
    return causal_lm_loss(logits if b is None else logits + b, y)


def _rel(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return np.max(np.abs(got - want)) / np.max(np.abs(want))


@pytest.mark.parametrize("cotangent", [1.0, 1024.0, 1 / 3])
@pytest.mark.parametrize("B", [1, 6])
@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("transpose", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_value_and_gradients_are_those_of_the_plain_loss(dtype, transpose,
                                                         bias, B, cotangent):
    """One and three chunks (``batch_chunk`` 2), with the loss scale as the
    cotangent, or a third (a caller's own weight on the loss, which bfloat16
    does not hold: it multiplies in float32). float32 to 1e-5. bfloat16 to
    two units in the last place of the largest entry: the parent's own
    autodiff of the checkpointed scan read up to 2**-7 against this
    reference on the CPU (three chunks' ``dw``), where nothing rounds
    ``dlogits``; the TPU's default precision rounds it to bfloat16 in both,
    as this rule does everywhere."""
    x, w, b, y = _inputs(dtype, B, transpose, bias)
    argnums = (0, 1, 2) if bias else (0, 1)

    def fused(x, w, b):
        return cotangent * chunked_causal_lm_loss(
            x, w, y, batch_chunk=2, transpose=transpose, head_bias=b)

    def plain(x, w, b):
        return cotangent * _plain(x, w, b, y, transpose)

    got, got_grads = jax.value_and_grad(fused, argnums)(x, w, b)
    want, want_grads = jax.value_and_grad(plain, argnums)(x, w, b)
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -6
    np.testing.assert_allclose(got, want, rtol=1e-5 if dtype == jnp.float32
                               else 2e-3)
    for g, wnt, arg in zip(got_grads, want_grads, (x, w, b)):
        assert g.dtype == arg.dtype and g.shape == arg.shape
        assert _rel(g, wnt) <= tol
    # the last position predicts nothing
    assert not np.any(np.asarray(got_grads[0][:, -1], np.float32))
    # no gradient asked: the plain scan gives the same value
    np.testing.assert_allclose(fused(x, w, b), got, rtol=1e-6)


@pytest.mark.parametrize("B", [2, 6])
def test_the_cotangent_multiplies_float32_values(B):
    """A cotangent that bfloat16 does not hold (a third) scales what was
    kept in float32, rounded once: to the bit what the float32 product of
    the unscaled gradients rounds to, which a cotangent rounded to bfloat16
    first misses by up to 2**-9 of every entry."""
    x, w, b, y = _inputs(jnp.bfloat16, B, False, True)
    third = jnp.float32(1 / 3)

    def grads(scale):
        return jax.grad(lambda x, w, b: scale * chunked_causal_lm_loss(
            x, w, y, batch_chunk=2, head_bias=b), (0, 1, 2))(x, w, b)

    for got, one in zip(grads(third), grads(1.0)):
        want = (one.astype(jnp.float32) * third).astype(one.dtype)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        assert np.any(np.asarray(got, np.float32) != np.asarray(
            one * third.astype(one.dtype), np.float32)) or \
            one.dtype == jnp.float32


def test_fp16_gradients_are_scaled_in_float32_before_the_cast_back():
    """fp16 models take the float32 path, so a gradient that would vanish
    in fp16 before the loss scale multiplies it arrives scaled."""
    x, w, _, y = _inputs(jnp.float16, 6, True, False)
    scale = 2.0 ** 14

    def fused(x, w):
        return scale * chunked_causal_lm_loss(x, 1e-4 * w, y, batch_chunk=2,
                                              transpose=True)

    gx, gw = jax.grad(fused, (0, 1))(x, w)
    want = jax.grad(lambda x, w: scale * _plain(x, 1e-4 * w, None, y, True),
                    (0, 1))(x, w)
    assert gx.dtype == gw.dtype == jnp.float16
    assert np.count_nonzero(np.asarray(gx)) > gx.size // 2
    assert _rel(gx, want[0]) <= 2.0 ** -9 and _rel(gw, want[1]) <= 2.0 ** -9


@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("B", [2, 6])
def test_nothing_of_v_columns_is_kept_but_the_weights_gradient(B, bias,
                                                               capsys):
    """What the forward pass keeps for the backward pass: ``dh``, ``dw`` (and
    ``dbias``) — no logits, no softmax, and not ``x`` for a second product."""
    from jax.ad_checkpoint import print_saved_residuals
    x, w, b, y = _inputs(jnp.bfloat16, B, True, bias)

    def loss(x, w, b):
        return chunked_causal_lm_loss(x, w, y, batch_chunk=2, transpose=True,
                                      head_bias=b)

    jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(loss, *a)[1])(x, w, b).jaxpr
    kept = sorted((v.aval.shape, str(v.aval.dtype)) for v in jaxpr.outvars)
    want = [((B, T - 1, C), "bfloat16"), ((C, V), "bfloat16")]
    assert kept == sorted(want + ([((V,), "float32")] if bias else []))
    print_saved_residuals(loss, x, w, b)
    listed = capsys.readouterr().out
    assert f"bf16[{B},{T - 1},{C}]" in listed and f"bf16[{C},{V}]" in listed
    assert not re.search(rf"\[\d+,\d+,{V}\]", listed)


def _head_dots(fn, *args) -> int:
    """Products in the compiled program (the head is all it holds)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return len(re.findall(r"= \S+ dot\(", text))


@pytest.mark.parametrize("B", [2, 6])
def test_the_compiled_gradient_has_three_products_and_two_for_x_alone(B):
    """``x @ w``, ``dlogits @ w^T`` and ``h^T @ dlogits``: not a second
    ``x @ w``. With the head frozen the weight's gradient is dead work, and
    forward and backward rules are one module, so the compiler drops it."""
    x, w, _, y = _inputs(jnp.float32, B, True, False)

    def loss(x, w):
        return chunked_causal_lm_loss(x, w, y, batch_chunk=2, transpose=True)

    assert _head_dots(loss, x, w) == 1
    assert _head_dots(jax.grad(loss, (0, 1)), x, w) == 3
    assert _head_dots(jax.grad(loss, 0), x, w) == 2


def test_sharded_over_fsdp_equals_unsharded():
    """The batch's rows and the weight's rows over the eight devices, as the
    ZeRO-3 step holds them: the partial sums of ``dw`` reduce to the
    unsharded gradient."""
    B = 16
    x, w, b, y = _inputs(jnp.float32, B, True, True)

    def grads(x, w, b, y):
        return jax.value_and_grad(
            lambda x, w, b: chunked_causal_lm_loss(
                x, w, y, batch_chunk=8, transpose=True, head_bias=b),
            (0, 1, 2))(x, w, b)

    want = jax.jit(grads)(x, w, b, y)
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("fsdp",))
    rows, rep = (NamedSharding(mesh, s) for s in (P("fsdp"), P()))
    got = jax.jit(grads, in_shardings=(rows, rows, rep, rows),
                  out_shardings=(rep, (rows, rows, rep)))(x, w, b, y)
    for g, wnt in zip(jax.tree_util.tree_leaves(got),
                      jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, wnt, rtol=1e-5, atol=1e-7)


def test_abstract_traces_pass_and_the_counter_says_when_it_engaged():
    """``jax.eval_shape`` (the remat plan's trace of the loss) and
    ``jax.linearize`` pass through the rule as through the flash kernel's;
    ``train/loss_head/fused`` is noted when a gradient is traced, not by an
    evaluation."""
    x, w, _, y = _inputs(jnp.bfloat16, 6, False, False)

    def loss(x, w):
        return chunked_causal_lm_loss(x, w, y, batch_chunk=2)

    with tracer._totals_lock:
        tracer.totals.pop("train/loss_head/fused", None)
    assert jax.eval_shape(loss, x, w).shape == ()
    float(jax.jit(loss)(x, w))
    assert "train/loss_head/fused" not in tracer.totals
    value, lin = jax.linearize(loss, x, w)
    assert tracer.totals["train/loss_head/fused"] == 6
    out = jax.make_jaxpr(lambda x, w: jax.linearize(loss, x, w)[1])(x, w)
    assert all(V not in v.aval.shape or v.aval.shape == (V, C)
               for v in out.jaxpr.outvars)
    np.testing.assert_allclose(value, loss(x, w), rtol=1e-6)
    # every operation of the rule carries the scope a device trace reads
    text = jax.jit(jax.grad(loss, (0, 1))).lower(x, w).compile().as_text()
    dots = [l for l in text.splitlines() if re.search(r"= \S+ dot\(", l)]
    assert len(dots) == 3 and all("(loss_head)" in l for l in dots)


@pytest.mark.parametrize("loss", ["fused", "another"])
def test_the_engines_log_line_speaks_of_its_own_step(monkeypatch, loss):
    """Beside ``train/remat/*``: the line the engine logs when it has
    compiled its fitted step names the counter and its rows — of THIS step:
    what an earlier trace in the process noted (here 99 rows) is not said
    of a step whose loss is another."""
    import deepspeed_tpu
    from deepspeed_tpu.accelerator import get_accelerator
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.utils.logging import logger
    lines = []
    monkeypatch.setattr(logger, "info", lambda msg, *a: lines.append(
        msg % a if a else msg))
    monkeypatch.setattr(logger, "log",
                        lambda level, msg, *a: lines.append(str(msg)))
    monkeypatch.setattr(type(get_accelerator()), "total_memory",
                        lambda self, device_index=None: 1 << 30)
    if loss == "another":
        monkeypatch.setattr(
            llama, "chunked_causal_lm_loss",
            lambda x, w, labels, batch_chunk, transpose: causal_lm_loss(
                x.astype(jnp.float32) @ w.astype(jnp.float32), labels))
    tracer.note("train/loss_head/fused", 99)
    model = llama.LlamaForCausalLM(llama.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, remat=True))
    engine, *_ = deepspeed_tpu.initialize(
        model=model, rngs=jax.random.PRNGKey(0),
        config={"train_batch_size": 8, "steps_per_print": 0,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3}, "mesh": {"fsdp": 8}})
    batch = {"input_ids": np.arange(8 * 64, dtype=np.int32).reshape(8, 64)
             % 128}
    assert np.isfinite(float(engine.train_batch(batch)))
    said = [l for l in lines if "activation checkpointing: rung" in l]
    assert len(said) == 1
    if loss == "fused":
        assert tracer.totals["train/loss_head/fused"] == 8
        assert "train/loss_head/fused" in said[0] and "over 8 rows" in said[0]
    else:
        assert tracer.totals["train/loss_head/fused"] == 0
        assert "loss_head" not in said[0]
    engine.destroy()
