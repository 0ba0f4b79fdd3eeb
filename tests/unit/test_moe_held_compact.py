"""The compact path of a held share of experts (``ragged_model._moe_ffn``
handed a count of ``turns``; docs/SERVING.md "Held experts") against a plain
statement of what the share computes: for every token, the gate-weighted sum
of the outputs of those of its chosen experts this chip holds, each expert a
dense product over the token's row, in float32.

One parametrised test: how the router's choices fall on the held experts
(evenly; none; exactly the bound of one turn; one more; every choice, which
takes several turns and says so in the count; the skip id of a router that
may choose no expert; a pass whose last rows are padding — all alike, marked
not ``live``, and so asking no expert — and whose choices are no whole number
of slabs) x the grouped kernel (``pallas``: bfloat16
stacks of whole lane tiles; ``xla``: float32) x the experts' form (SwiGLU,
or two matrices around relu^2).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import ragged_model as rm

ROUTED, FIRST, HELD, TOP_K, HID, FFN = 16, 4, 2, 4, 128, 128
CASES = ("uniform", "none_held", "exactly_bound", "bound_plus_one",
         "every_choice", "skip_id", "pad_rows")


def plain(x, w, gates, ids, act):
    """The held share's output, a dense product an expert, float32."""
    f32 = lambda a: np.asarray(a, np.float32)
    x, out = f32(x), np.zeros(x.shape, np.float32)
    for e in range(HELD):
        up = x @ f32(w["w_up"][e])
        if "w_gate" in w:
            g = x @ f32(w["w_gate"][e])
            h = g / (1.0 + np.exp(-g)) * up
        else:
            h = f32(act(jnp.asarray(up)))
        y = h @ f32(w["w_down"][e])
        weight = np.where(ids == FIRST + e, gates, 0.0).sum(axis=1)
        out += weight[:, None] * y
    return out


def choices(case, rng, tokens, bound):
    """``ids [T, K]`` of the case: how many choices land on the held
    experts, and (skip_id) some that are the choice of no expert."""
    n = tokens * TOP_K
    others = np.setdiff1d(np.arange(ROUTED), np.arange(FIRST, FIRST + HELD))
    if case in ("uniform", "pad_rows", "skip_id"):
        ids = np.stack([rng.permutation(ROUTED + (case == "skip_id"))[:TOP_K]
                        for _ in range(tokens)])
        return ids.astype(np.int32)
    held = {"none_held": 0, "exactly_bound": bound,
            "bound_plus_one": bound + 1, "every_choice": n}[case]
    flat = rng.choice(others, size=n)
    at = rng.permutation(n)[:held]
    flat[at] = FIRST + rng.integers(0, HELD, size=held)
    return flat.reshape(tokens, TOP_K).astype(np.int32)


@pytest.mark.parametrize("form", ["swiglu", "two_matrix"])
@pytest.mark.parametrize("kernel", ["pallas", "xla"])
@pytest.mark.parametrize("case", CASES)
def test_the_compact_path_gives_the_held_shares_output(case, kernel, form):
    dtype = jnp.bfloat16 if kernel == "pallas" else jnp.float32
    tokens = 50 if case == "pad_rows" else 48
    rng = np.random.default_rng(CASES.index(case))
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(keys[0], (tokens, HID), jnp.float32)
    live = None
    if case == "pad_rows":          # a pass's padding: rows all alike
        x = x.at[tokens - 14:].set(x[0])
        live = jnp.arange(tokens) < tokens - 14
    x = x.astype(dtype)
    stack = lambda k, a, b: (jax.random.normal(k, (HELD, a, b), jnp.float32)
                             * a ** -0.5).astype(dtype)
    w = {"w_up": stack(keys[1], HID, FFN), "w_down": stack(keys[2], FFN, HID)}
    if form == "swiglu":
        w["w_gate"] = stack(keys[3], HID, FFN)
    assert rm.moe_grouped_kernel(w["w_up"], dtype) == kernel
    routing = {"num_experts": ROUTED, "top_k": TOP_K,
               "held": (FIRST, HELD), "act": "relu2"}
    if case == "skip_id":
        routing["skip"] = True
    bound = rm.held_rows_bound(tokens * TOP_K, HELD,
                               ROUTED + (case == "skip_id"), kernel)
    assert bound is not None and bound < tokens * TOP_K
    ids = choices(case, rng, tokens, bound)
    gates = rng.uniform(0.1, 1.0, ids.shape).astype(np.float32)
    if live is not None:            # .. and routed alike, to a held expert
        ids[tokens - 14:] = [FIRST, FIRST + 1, 0, 1]
    asked = ids if live is None else np.where(np.asarray(live)[:, None], ids,
                                              ROUTED)
    n_held = int(((asked >= FIRST) & (asked < FIRST + HELD)).sum())

    run = jax.jit(lambda x, gates, ids, turns: rm._moe_ffn(
        x, w, TOP_K, jnp.float32, routing=routing, routed=(gates, ids),
        turns=turns, live=live))
    out, turns = run(x, jnp.asarray(gates), jnp.asarray(ids), jnp.int32(5))

    want = plain(x, w, gates, asked, rm._plain_act("relu2"))
    scale = np.abs(want).max() + 1e-6
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    assert np.abs(np.asarray(out) - want).max() <= tol * scale
    # the parent's path, every choice sorted and combined, says the same
    older = jax.jit(lambda x, gates, ids: rm._moe_ffn(
        x, w, TOP_K, jnp.float32, routing=routing, routed=(gates, ids)))(
            x, jnp.asarray(gates), jnp.asarray(ids))
    rows = slice(None) if live is None else np.asarray(live)
    assert np.abs(np.asarray(older)[rows] - want[rows]).max() <= tol * scale
    # turns past the first, added to the count the pass handed in
    over = max(-(-n_held // bound) - 1, 0)
    assert int(turns) == 5 + over
    assert {"none_held": n_held == 0, "exactly_bound": over == 0,
            "bound_plus_one": over == 1, "every_choice": over >= 2}.get(
                case, True)


def test_a_share_that_gives_no_bound_takes_the_path_it_always_took():
    """Half the experts held (granite, nemotron_h), every expert beside a
    skip id (zaya): twice the even share is every choice, no bound, and the
    count comes back as it went in."""
    assert rm.held_rows_bound(8448, 36, 72, "pallas") is None
    assert rm.held_rows_bound(2112, 16, 17, "xla") is None
    assert rm.held_rows_bound(21120, 64, 512, "pallas") == 5376
    assert rm.held_rows_bound(8448, 16, 256, "pallas") == 1152
    x = jnp.ones((8, HID), jnp.float32)
    w = {"w_up": jnp.ones((8, HID, FFN)), "w_down": jnp.ones((8, FFN, HID))}
    ids = jnp.tile(jnp.arange(TOP_K, dtype=jnp.int32), (8, 1))
    routing = {"num_experts": 16, "top_k": TOP_K, "held": (0, 8)}
    out, turns = rm._moe_ffn(x, w, TOP_K, jnp.float32, routing=routing,
                             routed=(jnp.ones((8, TOP_K)), ids),
                             turns=jnp.int32(3))
    assert int(turns) == 3 and out.shape == (8, HID)
