"""The serving programs of the families the benchmark runs, as lowered text.

Nine families at toy widths (dense llama lineage, Mixtral, Trinity's afmoe,
Jamba, JoyAI; granite, recorded later: ``LATER``; Nemotron-H, Qwen3-Next and
ZAYA, later still: ``NEWER``) x three programs
(decode step, paged pass, packed prefill), lowered for the CPU — where the
Pallas kernels lower as their interpreted bodies, so the kernels' own text is
held too — and hashed. ``data/serving_program_text.json`` holds the hashes of
the commit before PR 39 (12c8bb9): a change that means to leave these
programs as they are (a new family beside them, a spec field that is None
for them) passes without touching that file. A change that means to change
them writes the file anew and says so::

    PYTHONPATH=. JAX_PLATFORMS=cpu python \
        tests/unit/test_serving_program_text.py --write

(``--write <file> <commit>`` with ``PYTHONPATH`` at a ``git archive`` of
another commit records that commit's programs: how the file was made;
``--write <file> <commit> <name>..`` records those families or those
programs alone and merges them into the file under ``later``: how granite's
were, from 83a3dac, and how every family's decode step was by PR 45, whose
tree took the step loop — a ``lax.scan`` of length one around the pass —
out of that program's text, and how the K/V families' paged pass was by PR
49, whose chunk kernel walks a slot's own pages in groups — JoyAI's paged
pass, over latent pages, kept its hash; and how jamba's and zaya's paged
pass was by PR 62 (``--write <file> "PR 62 on 96dbd0a" serve_paged_pass``
into a copy, the two hashes that differ taken from it): the only toy
programs whose heads are 128 wide, so the only ones whose chunk slots' K/V
rows go to the pages as runs (``paged_attention.paged_kv_run_write``) — the
narrow-headed families' paged pass, JoyAI's, every decode step and every
packed prefill kept their hashes, which is how cells 8 and 14 and every
decode step are known to run what they ran; the packed prefill of all six is
still the older commits'; and how PR 51 recorded the nine programs of
``NEWER`` and, anew, jamba's decode step and paged pass — with zaya's the
only toy programs whose heads are 128 wide, so the only ones that hold the
paged decode kernel PR 51 rewrote: ``--write <file> "PR 51 on c92e0a8"
nemotron_h qwen3_next zaya``, then ``... jamba serve_decode_step
serve_paged_pass``; the other twenty-three hashes are what c92e0a8 lowers
to, and so are the packed prefills of the three new families).

Beside the hashes: which of its two forms each family's toy decode step
takes (``ragged_model.side_buffer_fits``), and that the traced step holds
no loop but its layer scans.
"""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "serving_program_text.json")
FAMILIES = ("llama", "mixtral", "afmoe", "jamba", "joyai")
#: granite's four programs (Mamba-2 with ONE group of B and C), recorded from
#: the commit before PR 42 gave the SSD kernels a group axis (83a3dac, the
#: file's ``later``): one group lowers to the text it lowered to
LATER = ("granite",)
#: the three families that came after (PRs 42, 47, 50), recorded by PR 51 on
#: the tree it left: Mamba-2 with two groups under relu^2 experts, Gated
#: DeltaNet beside gated attention, and compressed convolutional attention
#: (heads 128 wide: its decode step and paged pass hold the paged kernels)
NEWER = ("nemotron_h", "qwen3_next", "zaya")
#: Qwen3-Next's toy model with 2 of 16 experts held, recorded by PR 53: the
#: one toy spec whose share gives its programs a bound
#: (``ragged_model.held_rows_bound``: 32 of the paged pass's 108 choices, 24
#: of the packed prefill's 96, 8 of the decode step's 12), so the one whose
#: programs hold the compact path of ``_moe_ffn``
HELD = ("qwen3_next_held",)
PROGRAMS = ("serve_decode_step", "serve_paged_pass", "serve_prefill_packed")


def tiny(fam, model=None):
    """(spec, weights, pools) of a family at toy widths, float32; ``model``
    is ``(config, module, adapter)`` of a family this file does not name."""
    from deepspeed_tpu.inference.v2 import adapters, model_spec as ms
    from deepspeed_tpu.inference.v2.ragged.state_pool import (StatefulKV,
                                                              StatePoolConfig)
    key, ids = jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    f32 = jnp.float32
    if model is not None:
        cfg, model, adapt = model
    elif fam == "jamba":
        from deepspeed_tpu.models.jamba import JambaConfig, JambaForCausalLM
        cfg = JambaConfig.tiny(dtype=f32, hidden_size=256,
                               num_attention_heads=2, mamba_dt_rank=16)
        model, adapt = JambaForCausalLM(cfg), adapters.adapt_jamba
    elif fam == "mixtral":
        from deepspeed_tpu.models.mixtral import (MixtralConfig,
                                                  MixtralForCausalLM)
        cfg = MixtralConfig.tiny(dtype=f32)
        model, adapt = MixtralForCausalLM(cfg), adapters.adapt_llama
    elif fam == "afmoe":
        from deepspeed_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM
        cfg = AfmoeConfig.tiny(dtype=f32)
        model, adapt = AfmoeForCausalLM(cfg), adapters.adapt_afmoe
    elif fam == "joyai":
        from deepspeed_tpu.models.joyai import JoyaiConfig, JoyaiForCausalLM
        cfg = JoyaiConfig.tiny(dtype=f32)
        model, adapt = JoyaiForCausalLM(cfg), adapters.adapt_joyai
    elif fam == "granite":
        from deepspeed_tpu.models.granite import (GraniteConfig,
                                                  GraniteForCausalLM)
        cfg = GraniteConfig.tiny(dtype=f32)
        model, adapt = GraniteForCausalLM(cfg), adapters.adapt_granite
    elif fam == "nemotron_h":
        from deepspeed_tpu.models.nemotron_h import (NemotronHConfig,
                                                     NemotronHForCausalLM)
        cfg = NemotronHConfig.tiny(dtype=f32)
        model, adapt = NemotronHForCausalLM(cfg), adapters.adapt_nemotron_h
    elif fam in ("qwen3_next", "qwen3_next_held"):
        from deepspeed_tpu.models.qwen3_next import (Qwen3NextConfig,
                                                     Qwen3NextForCausalLM)
        held = dict(num_experts=16, experts_held=(2, 2)) if fam in HELD else {}
        cfg = Qwen3NextConfig.tiny(dtype=f32, **held)
        model, adapt = Qwen3NextForCausalLM(cfg), adapters.adapt_qwen3_next
    elif fam == "zaya":
        from deepspeed_tpu.models.zaya import ZayaConfig, ZayaForCausalLM
        cfg = ZayaConfig.tiny(dtype=f32)
        model, adapt = ZayaForCausalLM(cfg), adapters.adapt_zaya
    else:
        from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig.tiny(dtype=f32)
        model, adapt = LlamaForCausalLM(cfg), adapters.adapt_llama
    params = model.init(key, ids)["params"]
    spec, weights = adapt(params, cfg)
    spec.dtype = f32
    if spec.mla is not None:
        return spec, weights, jnp.zeros(
            (spec.num_layers, 9, 16, ms.latent_width(spec)), f32)
    pages = jnp.zeros((max(1, ms.num_page_layers(spec)), 9, 2,
                       spec.num_kv_heads, 16, spec.head_dim), f32)
    # the state pool as the engine sizes it (``engine_v2.py``), 4 slots
    if spec.mamba is not None:
        m = spec.mamba
        pool = StatePoolConfig(
            ms.num_state_layers(spec), 4, m["d_inner"], m["d_state"],
            m["d_conv"],
            conv_dim=m["d_inner"] + 2 * m.get("n_groups", 1) * m["d_state"]
            if m.get("kind") == "mamba2" else m.get("conv_dim"))
    elif spec.cca is not None:
        pool = StatePoolConfig.tails_only(
            ms.num_state_layers(spec), 4, taps=spec.cca["taps"],
            channels=spec.cca["tail_channels"])
    else:
        return spec, weights, pages
    return spec, weights, StatefulKV(pages, *pool.zeros())


def programs(spec):
    """``{name: (program, arguments after weights and pools)}``."""
    from deepspeed_tpu.inference.v2 import ragged_model as rm
    from deepspeed_tpu.inference.v2.ragged.ragged_batch import RaggedBatch
    host = RaggedBatch(num_slots=2, slot_size=16, max_sequences=4,
                       max_blocks=16).device_arrays()
    pooled = spec.mamba is not None or spec.cca is not None
    state = rm.STATE_PASS_KEYS if pooled else ()
    pick = lambda keys: {k: jnp.zeros((4,), jnp.int32) if host[k] is None
                         else jnp.asarray(host[k]) for k in keys + state}
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    step = (i32(4), i32(4), i32(4, 16), i32(4) + 1,
            jax.random.PRNGKey(0), jnp.float32(1.0))
    if pooled:
        step += (i32(4),)
    return {
        "serve_decode_step": (rm.build_decode_step(spec), step),
        "serve_paged_pass": (rm.build_ragged_forward(spec),
                             (pick(rm.PAGED_PASS_KEYS),)),
        "serve_prefill_packed": (rm.build_prefill_forward(spec),
                                 (pick(rm.PREFILL_PASS_KEYS),)),
    }


def lowered(spec, weights, kv, program):
    fwd, args = programs(spec)[program]
    return jax.jit(fwd).lower(weights, kv, *args).as_text()


def digest(text):
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("fam", FAMILIES + LATER + NEWER + HELD)
def test_program_lowers_to_the_recorded_text(fam, program, golden):
    assert golden["jax"] == jax.__version__, (
        "another jax lowers to other text: write the file anew on the commit "
        "it records, with this jax")
    text = lowered(*tiny(fam), program)
    later = golden["later"]
    commit = later.get(fam) or later.get(program) or golden["commit"]
    assert digest(text) == golden["programs"][f"{fam}.{program}"], (
        f"{fam}'s {program} is not the text that {commit} lowers "
        "to: if that is meant, write the file anew (module docstring)")


#: the form each family's toy decode step takes: heads 128 wide on one
#: device take the side buffer, narrower ones the in-layer write; JoyAI's
#: latent pages have a builder of their own and the question is not asked
SIDE_BUFFER = {"llama": False, "mixtral": False, "afmoe": False,
               "jamba": True, "joyai": None, "granite": False,
               "nemotron_h": False, "qwen3_next": False, "zaya": True,
               "qwen3_next_held": False}


@pytest.mark.parametrize("fam", FAMILIES + LATER + NEWER + HELD)
def test_the_form_each_familys_decode_step_takes(fam, monkeypatch):
    from deepspeed_tpu.inference.v2 import ragged_model as rm
    spec = tiny(fam)[0]
    asked = []
    fits = rm.side_buffer_fits
    monkeypatch.setattr(rm, "side_buffer_fits", lambda *a: (
        asked.append(fits(*a)), asked[-1])[1])
    rm.build_decode_step(spec)
    assert asked == [] if SIDE_BUFFER[fam] is None else [SIDE_BUFFER[fam]]
    assert (spec.mla is not None) == (SIDE_BUFFER[fam] is None)


def _wide(**kw):
    """A spec at Mistral-7B's heads (32 over 8, 128 wide, window 4096)."""
    from deepspeed_tpu.inference.v2.model_spec import RaggedModelSpec
    return RaggedModelSpec(**{**dict(
        family="llama", num_layers=2, hidden_size=4096, num_heads=32,
        num_kv_heads=8, head_dim=128, vocab_size=128, window=4096), **kw})


@pytest.mark.parametrize("spec,tp,ring_ok,lora,fits", [
    pytest.param({}, 1, True, None, True, id="wide_heads_ring_checked"),
    pytest.param({"window": None}, 1, False, None, True, id="no_window"),
    pytest.param({}, 2, True, None, False, id="tensor_parallel"),
    pytest.param({"head_dim": 64}, 1, True, None, False, id="head_dim_64"),
    pytest.param({}, 1, True, ("wq", "wv"), False, id="lora_targets"),
    pytest.param({}, 1, False, None, False, id="window_ring_not_checked"),
])
def test_side_buffer_fits(spec, tp, ring_ok, lora, fits):
    from deepspeed_tpu.inference.v2 import ragged_model as rm
    assert rm.side_buffer_fits(_wide(**spec), tp, ring_ok, lora) is fits


def _top_level_loops(jaxpr):
    """Lengths of the loops of a jaxpr that no other loop holds (``None`` for
    a ``while``); what a call or a jit wraps counts as where the call is, a
    Pallas kernel's body is its own."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "scan":
            found.append(eqn.params["length"])
        elif name == "while":
            found.append(None)
        elif name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += _top_level_loops(sub)
    return found


@pytest.mark.parametrize("fam", FAMILIES + LATER + NEWER + HELD)
def test_the_traced_decode_step_holds_no_loop_but_its_layer_scans(fam):
    """One program decodes one token: the step's only loops are the scans
    over its units of layers (``layer_units``), in order, each as long as its
    unit repeats; no loop over steps holds them."""
    from deepspeed_tpu.inference.v2 import model_spec as ms
    spec, weights, kv = tiny(fam)
    fwd, args = programs(spec)["serve_decode_step"]
    jaxpr = jax.make_jaxpr(fwd)(weights, kv, *args)
    assert _top_level_loops(jaxpr.jaxpr) == [
        n for _, _, n in ms.layer_units(spec)]


@pytest.mark.parametrize("program,choices,bound", [
    ("serve_paged_pass", 108, 32), ("serve_prefill_packed", 96, 24),
    ("serve_decode_step", 12, 8)])
def test_the_held_toy_specs_programs_take_the_compact_path(program, choices,
                                                           bound):
    """2 of 16 held: each program has a bound under its choices, returns its
    count of overflow turns after its three results, and its MoE layers hold
    the slab loop (a ``while`` inside their layer scans, which the test
    above does not count); the same programs of the model that holds every
    expert have three results."""
    from deepspeed_tpu.inference.v2 import ragged_model as rm
    spec, weights, kv = tiny("qwen3_next_held")
    assert spec.moe["held"] == (2, 2)
    assert rm.pass_held_rows_bound(spec, weights, choices // 3) == bound
    fwd, args = programs(spec)[program]
    out = jax.eval_shape(fwd, weights, kv, *args)
    assert len(out) == 4 and out[3].shape == () and out[3].dtype == jnp.int32
    whole, whole_weights, whole_kv = tiny("qwen3_next")
    assert len(jax.eval_shape(programs(whole)[program][0], whole_weights,
                              whole_kv, *args)) == 3


if __name__ == "__main__":
    assert sys.argv[1] == "--write"
    import subprocess
    out = sys.argv[2] if len(sys.argv) > 2 else GOLDEN
    import deepspeed_tpu
    where = os.path.dirname(os.path.dirname(deepspeed_tpu.__file__))
    commit = sys.argv[3] if len(sys.argv) > 3 else subprocess.run(
        ["git", "-C", where, "rev-parse", "--short", "HEAD"],
        capture_output=True, text=True).stdout.strip()
    # ``--write <file> <commit> <name>..``: those families or programs
    # only, merged into ``<file>`` under ``later`` (recorded from a later
    # commit than the file's own)
    only = tuple(sys.argv[4:])
    every = FAMILIES + LATER + NEWER + HELD
    fams_ = [n for n in only if n in every] or every
    hashes = {}
    for fam_ in fams_:
        model_ = tiny(fam_)
        for program_ in [n for n in only if n in PROGRAMS] or PROGRAMS:
            hashes[f"{fam_}.{program_}"] = digest(lowered(*model_, program_))
    if only:
        with open(out) as f:
            record = json.load(f)
        record["programs"].update(hashes)
        record.setdefault("later", {}).update({f: commit for f in only})
    else:
        record = {"commit": commit, "jax": jax.__version__,
                  "later": {f: commit for f in LATER + NEWER + HELD},
                  "programs": hashes}
    with open(out, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
