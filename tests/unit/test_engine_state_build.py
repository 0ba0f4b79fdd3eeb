"""The train engine's lazy state build (``runtime/engine.py::_init_state``,
``train_state_build_lazy``) is ONE program whatever the run: what differs by
run — the init key — is its argument, not a constant in its text. The
persistent cache keys a program by its text, so a key in the text made every
seed another program and no run ever loaded the build it asked for. The cases
read the lowered text (two seeds, two first batches), hold the values against
the closure form the engine had before (kept here as the control), and count
what a second engine at another seed asks of the back end."""

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.monitor.trace import tracer
from deepspeed_tpu.utils import compile_cache

PROGRAM = "train_state_build_lazy"
#: name -> (devices, the engine's mesh): one device, and the eight-device mesh
#: with ZeRO-3 over four
MESHES = {"one_device": (1, {"data": 1}), "fsdp4": (8, {"data": 2, "fsdp": 4})}


class _Spy:
    """``jax.jit`` while an engine builds its state: jits as ``jax.jit`` does
    and keeps the build's function, options and arguments. With
    ``form="closure"`` the control runs in the build's place."""

    real = staticmethod(jax.jit)

    def __init__(self, form="argument"):
        self.form, self.fn, self.options, self.args = form, None, None, None

    def __call__(self, fn, **options):
        jitted = self.real(fn, **options)
        if getattr(fn, "__name__", "") != PROGRAM:
            return jitted

        def call(*args):
            self.fn, self.options, self.args = fn, options, args
            return (self.closure_form()() if self.form == "closure"
                    else jitted(*args))
        return call

    def text(self) -> str:
        return self.real(self.fn, **self.options).lower(*self.args).as_text()

    def closure_form(self):
        """The build as the engine jitted it before: a program of no
        arguments, the key a constant of its text."""
        fn, args = self.fn, self.args

        def train_state_build_lazy():
            return fn(*args)
        return self.real(train_state_build_lazy,
                         out_shardings=self.options["out_shardings"])


def _built(monkeypatch, mesh, seed, batch_seed=0, form="argument"):
    """An engine whose state was built from its first batch, and the spy that
    watched."""
    devices, axes = MESHES[mesh]
    if len(jax.devices()) < devices:
        pytest.skip(f"needs {devices} virtual devices")
    from deepspeed_tpu.comm.mesh import build_topology
    from deepspeed_tpu.config import MeshConfig
    topo = build_topology(MeshConfig(**axes),
                          devices=jax.devices()[:devices])
    rows = 2 * topo.dp_world_size
    engine, *_ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(LlamaConfig.tiny(
            vocab_size=128, max_position_embeddings=64)),
        config={"train_batch_size": rows, "steps_per_print": 0,
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 3 if devices > 1 else 0},
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}},
        mesh_topology=topo, rngs=jax.random.PRNGKey(seed))
    ids = np.random.default_rng(batch_seed).integers(
        0, 128, (rows, 16)).astype(np.int32)
    spy = _Spy(form)
    with monkeypatch.context() as m:
        m.setattr(jax, "jit", spy)
        engine._ensure_state({"input_ids": ids, "labels": ids})
    assert spy.fn is not None, "the lazy build never ran"
    return engine, spy


@pytest.mark.parametrize("other", ["seed", "first_batch"])
@pytest.mark.parametrize("mesh", sorted(MESHES))
def test_the_lowered_build_holds_nothing_of_the_run(mesh, other, monkeypatch):
    engine, spy = _built(monkeypatch, mesh, seed=0, batch_seed=0)
    text = spy.text()
    engine.destroy()
    engine, spy = _built(monkeypatch, mesh, seed=int(other == "seed"),
                         batch_seed=int(other == "first_batch"))
    assert spy.text() == text
    # and the control can tell: the closure form's text holds the key
    if other == "seed":
        assert spy.closure_form().lower().as_text() != text
    engine.destroy()
    # the key is the program's one parameter
    assert len(spy.args) == 1 and spy.args[0].shape == (2,)
    assert "module @jit_train_state_build_lazy" in text


@pytest.mark.parametrize("mesh", sorted(MESHES))
def test_the_state_is_the_closure_forms_to_the_bit(mesh, monkeypatch):
    engine, spy = _built(monkeypatch, mesh, seed=7)
    control = spy.closure_form()()
    got, want = (jax.tree_util.tree_leaves_with_path(t)
                 for t in (engine.state, control))
    assert [p for p, _ in got] == [p for p, _ in want]
    assert len(got) > 20
    for (path, a), (_, b) in zip(got, want):
        name = jax.tree_util.keystr(path)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert a.sharding.is_equivalent_to(b.sharding, a.ndim), name
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), name
    # weights, not a tree of zeros: the key reached the initialisers
    assert float(np.abs(np.asarray(
        engine.state["master"]["embed_tokens"]["embedding"])).max()) > 0
    engine.destroy()


@pytest.fixture
def cache_in(tmp_path):
    """The persistent cache in a directory of this test's own, every program
    kept whatever it cost; the suite's own directory after it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_enable_compilation_cache")
    prior = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path), 0.0, -1, True)):
        jax.config.update(k, v)
    cc.reset_cache()
    yield tmp_path
    for k, v in prior.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _asked_of_the_back_end(build):
    """(programs that reached the back end, programs the cache gave,
    the build's events by name) while ``build()`` ran."""
    def row():
        phases = compile_cache.programs().get(PROGRAM, {})
        return sum(r[0] for r in phases.values())
    before, events = dict(tracer.totals), row()
    build()
    gained = {k: tracer.totals.get(k, 0) - before.get(k, 0)
              for k in ("compile/backend_compiles", "compile/cache_loads")}
    return (gained["compile/backend_compiles"], gained["compile/cache_loads"],
            row() - events)


@pytest.mark.parametrize("form,loaded", [("argument", True),
                                         ("closure", False)])
@pytest.mark.parametrize("mesh", sorted(MESHES))
def test_a_second_seed_loads_the_build_from_the_cache(mesh, form, loaded,
                                                      cache_in, monkeypatch):
    """``compile/backend_compiles`` counts every program that reached the
    back end, loaded or compiled (the event wraps the cache's lookup), and
    ``compile/cache_loads`` those the cache gave: a build that was LOADED
    raises both by one, one that was compiled only the first."""
    engines = []

    def build(seed):
        engines.append(_built(monkeypatch, mesh, seed=seed, batch_seed=seed,
                              form=form)[0])

    build(0)                                  # writes the program
    if not any(cache_in.iterdir()):
        pytest.skip("this backend's executables are not kept by the "
                    "persistent cache")
    reached, given, events = _asked_of_the_back_end(lambda: build(1))
    # traced, lowered and handed to the back end, once each
    assert events == 3
    assert reached >= 1
    assert (given == reached) is loaded, (reached, given)
    for e in engines:
        e.destroy()
