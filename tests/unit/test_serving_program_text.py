"""The serving programs of the families the benchmark runs, as lowered text.

Five families at toy widths (dense llama lineage, Mixtral, Trinity's afmoe,
Jamba, JoyAI; and granite, recorded later: ``LATER``) x four programs (decode step, fused multistep, paged pass,
packed prefill), lowered for the CPU — where the Pallas kernels lower as
their interpreted bodies, so the kernels' own text is held too — and hashed.
``data/serving_program_text.json`` holds the hashes of the commit before
PR 39 (12c8bb9): a change that means to leave these programs as they are
(a new family beside them, a spec field that is None for them) passes
without touching that file. A change that means to change them writes the
file anew and says so::

    PYTHONPATH=. JAX_PLATFORMS=cpu python \
        tests/unit/test_serving_program_text.py --write

(``--write <file> <commit>`` with ``PYTHONPATH`` at a ``git archive`` of
another commit records that commit's programs: how the file was made;
``--write <file> <commit> granite`` records that family alone from it and
merges it into the file: how granite's four were, from 83a3dac).
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
PROGRAMS = ("serve_decode_step", "serve_decode_multistep",
            "serve_paged_pass", "serve_prefill_packed")


def tiny(fam, model=None):
    """(spec, weights, pools) of a family at toy widths, float32; ``model``
    is ``(config, module, adapter)`` of a family this file does not name."""
    from deepspeed_tpu.inference.v2 import ragged_model as rm
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
        model, adapt = JambaForCausalLM(cfg), rm.adapt_jamba
    elif fam == "mixtral":
        from deepspeed_tpu.models.mixtral import (MixtralConfig,
                                                  MixtralForCausalLM)
        cfg = MixtralConfig.tiny(dtype=f32)
        model, adapt = MixtralForCausalLM(cfg), rm.adapt_llama
    elif fam == "afmoe":
        from deepspeed_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM
        cfg = AfmoeConfig.tiny(dtype=f32)
        model, adapt = AfmoeForCausalLM(cfg), rm.adapt_afmoe
    elif fam == "joyai":
        from deepspeed_tpu.models.joyai import JoyaiConfig, JoyaiForCausalLM
        cfg = JoyaiConfig.tiny(dtype=f32)
        model, adapt = JoyaiForCausalLM(cfg), rm.adapt_joyai
    elif fam == "granite":
        from deepspeed_tpu.models.granite import (GraniteConfig,
                                                  GraniteForCausalLM)
        cfg = GraniteConfig.tiny(dtype=f32)
        model, adapt = GraniteForCausalLM(cfg), rm.adapt_granite
    else:
        from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig.tiny(dtype=f32)
        model, adapt = LlamaForCausalLM(cfg), rm.adapt_llama
    params = model.init(key, ids)["params"]
    spec, weights = adapt(params, cfg)
    spec.dtype = f32
    if spec.mla is not None:
        return spec, weights, jnp.zeros(
            (spec.num_layers, 9, 16, rm.latent_width(spec)), f32)
    pages = jnp.zeros((max(1, rm.num_page_layers(spec)), 9, 2,
                       spec.num_kv_heads, 16, spec.head_dim), f32)
    if spec.mamba is None:
        return spec, weights, pages
    m = spec.mamba
    pool = StatePoolConfig(
        rm.num_state_layers(spec), 4, m["d_inner"], m["d_state"],
        m["d_conv"], **({"conv_dim": m["d_inner"] + 2 * m.get("n_groups", 1)
                         * m["d_state"]} if m.get("kind") == "mamba2" else {}))
    return spec, weights, StatefulKV(pages, *pool.zeros())


def programs(spec):
    """``{name: (program, arguments after weights and pools)}``."""
    from deepspeed_tpu.inference.v2 import ragged_model as rm
    from deepspeed_tpu.inference.v2.ragged.ragged_batch import RaggedBatch
    host = RaggedBatch(num_slots=2, slot_size=16, max_sequences=4,
                       max_blocks=16).device_arrays()
    state = rm.STATE_PASS_KEYS if spec.mamba is not None else ()
    pick = lambda keys: {k: jnp.zeros((4,), jnp.int32) if host[k] is None
                         else jnp.asarray(host[k]) for k in keys + state}
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    step = (i32(4), i32(4), i32(4, 16), i32(4) + 1,
            jax.random.PRNGKey(0), jnp.float32(1.0))
    if spec.mamba is not None:
        step += (i32(4),)
    return {
        "serve_decode_step": (rm.build_decode_step(spec), step),
        "serve_decode_multistep": (rm.build_multistep_decode(spec, 3), step),
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
@pytest.mark.parametrize("fam", FAMILIES + LATER)
def test_program_lowers_to_the_recorded_text(fam, program, golden):
    assert golden["jax"] == jax.__version__, (
        "another jax lowers to other text: write the file anew on the commit "
        "it records, with this jax")
    text = lowered(*tiny(fam), program)
    commit = golden["later"][fam] if fam in LATER else golden["commit"]
    assert digest(text) == golden["programs"][f"{fam}.{program}"], (
        f"{fam}'s {program} is not the text that {commit} lowers "
        "to: if that is meant, write the file anew (module docstring)")


if __name__ == "__main__":
    assert sys.argv[1] == "--write"
    import subprocess
    out = sys.argv[2] if len(sys.argv) > 2 else GOLDEN
    import deepspeed_tpu
    where = os.path.dirname(os.path.dirname(deepspeed_tpu.__file__))
    commit = sys.argv[3] if len(sys.argv) > 3 else subprocess.run(
        ["git", "-C", where, "rev-parse", "--short", "HEAD"],
        capture_output=True, text=True).stdout.strip()
    # ``--write <file> <commit> <family>..``: those families only, merged
    # into ``<file>`` under ``later`` (a family recorded from a later commit
    # than the file's own)
    only = tuple(sys.argv[4:])
    hashes = {}
    for fam_ in only or FAMILIES + LATER:
        model_ = tiny(fam_)
        for program_ in PROGRAMS:
            hashes[f"{fam_}.{program_}"] = digest(lowered(*model_, program_))
    if only:
        with open(out) as f:
            record = json.load(f)
        record["programs"].update(hashes)
        record.setdefault("later", {}).update({f: commit for f in only})
    else:
        record = {"commit": commit, "jax": jax.__version__,
                  "later": {f: commit for f in LATER}, "programs": hashes}
    with open(out, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
