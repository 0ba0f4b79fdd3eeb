"""The paged attention kernels' roofline shares (``chipbench/readers/paged.py``
over ``chipbench/reduce/kv_work.py`` and ``mla_work.py``): the yardstick's
bytes a token agree with the program's own pool arithmetic for every serving
configuration, the work of a call at sizes worked by hand, and the readers on
views written by hand — absent where there is nothing to read, the share
worked by hand where there is."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import ANNOTATIONS, Registry  # noqa: E402
from chipbench.reduce import hlo_names, kv_work, mla_work, xplane  # noqa: E402
from chipbench.reduce.xplane import DeviceTrace, Event, Trace  # noqa: E402
from deepspeed_tpu.monitor.trace import Capture  # noqa: E402
from tests.chipbench.test_named import SCOPED, kept_capture, mosaic  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SERVING = [c["name"] for c in BENCH["configs"]
           if "engine" in Registry().config(c["name"])]
#: (full layers, windowed layers, window) of each K/V serving configuration,
#: by hand from its file; the latent one has no K/V pages
LAYERS = {"mistral7b-serve-d16": (0, 16, 4096),
          "mixtral8x7b-serve-d3": (3, 0, None),
          "trinity-mini-serve-d6": (1, 5, 2048),
          "jamba2-3b-serve": (2, 0, None),
          "granite4-h-small-serve-ep2": (1, 0, None),
          "nemotron3-nano-serve-ep2": (2, 0, None),
          "qwen3-next-serve-ep8": (3, 0, None),
          "zaya1-8b-serve-pp2": (20, 0, None)}
LATENT = "joyai-flash-serve-ep16"
PEAKS = {"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 1e9}
#: 8 query heads over 2 KV heads of 128: 1 KiB a token a layer, 4 KiB of
#: queries and outputs a row, 4,096 operations a query-key pair
W = {"heads": 8, "kv_heads": 2, "head_dim": 128, "itemsize": 2,
     "block_size": 128, "token_bytes": 1024}


def paged():
    return Registry().module("readers", "paged")


def test_every_serving_configuration_is_covered():
    assert sorted(SERVING) == sorted(list(LAYERS) + [LATENT])


@pytest.mark.parametrize("name", sorted(LAYERS))
def test_a_tokens_bytes_are_the_pools(name):
    """The yardstick and the program must not disagree on a page's bytes:
    ``kv_work``'s bytes a token a layer, times the layers that attend and the
    page's tokens, are what the engine's own ``KVCacheConfig`` says a page
    of that layout takes (and what the file's memory account wrote down)."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.ragged.kv_cache import KVCacheConfig
    cfg = Registry().config(name)
    w = kv_work.widths(cfg)
    layers = w["full_layers"] + w["windowed_layers"]
    assert (w["full_layers"], w["windowed_layers"], w["window"]) \
        == LAYERS[name]
    pool = KVCacheConfig(layers, w["kv_heads"], w["head_dim"],
                         w["block_size"], 1, jnp.bfloat16)
    assert w["token_bytes"] * layers * w["block_size"] \
        == pool.bytes_per_block()
    assert w["itemsize"] == jnp.dtype(pool.dtype).itemsize
    account = cfg.get("memory_account_numbers", {})
    if "bytes_a_page" in account:
        assert account["bytes_a_page"] == pool.bytes_per_block()
    assert w["heads"] == cfg["num_attention_heads"]


def test_the_latent_configuration_has_no_kv_pages():
    """... and its yardstick counts the values attention needs of the row
    the pool pads to whole lane tiles."""
    cfg = Registry().config(LATENT)
    with pytest.raises((KeyError, AttributeError)):
        kv_work.widths(cfg)
    pool = cfg["memory_account_numbers"]["bytes_a_token_a_layer"]
    need = mla_work.row_bytes(cfg["kv_lora_rank"], cfg["qk_rope_head_dim"])
    assert need == 1152 and need <= pool == 1280


@pytest.mark.parametrize("window,max_context,windowed", [
    (8, 128, True), (64, 64, False)])
def test_the_windowed_layers_are_the_engines(window, max_context, windowed):
    """A tiny engine of the ``llama`` family: the layers ``kv_work`` calls
    windowed are the ones the engine does (a window no context can pass is
    none), and a token's bytes are its pool's."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    tiny = LlamaConfig.tiny(vocab_size=128, max_position_embeddings=128,
                            sliding_window=window, dtype=jnp.bfloat16)
    model = LlamaForCausalLM(tiny)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    engine_cfg = {"state_manager": {"max_tracked_sequences": 4,
                                    "max_ragged_sequence_count": 4,
                                    "max_ragged_batch_size": 32,
                                    "max_context": max_context},
                  "kv_cache": {"block_size": 16, "num_blocks": 8}}
    engine = InferenceEngineV2(model=model, model_parameters=params,
                               config=dict(engine_cfg, dtype=jnp.bfloat16))
    w = kv_work.widths({
        "family": "llama", "num_hidden_layers": tiny.num_hidden_layers,
        "num_attention_heads": tiny.num_attention_heads,
        "num_key_value_heads": tiny.num_key_value_heads,
        "hidden_size": tiny.hidden_size, "sliding_window": window,
        "engine": engine_cfg})
    L = tiny.num_hidden_layers
    assert engine._windowed_layers == ([(window, L)] if windowed else [])
    assert (w["full_layers"], w["windowed_layers"], w["window"]) \
        == ((0, L, window) if windowed else (L, 0, None))
    assert w["token_bytes"] * L * w["block_size"] \
        == engine.kv.config.bytes_per_block()


def test_a_decode_call_by_hand():
    # two rows that hold 300 tokens in 4 whole pages between them
    flops, bytes_ = kv_work.decode_call(W, rows=2, ctx=300, pages=4)
    assert flops == 300 * 4096
    assert bytes_ == 4 * 128 * 1024 + 2 * 4096
    # a windowed layer reads the 200 tokens its queries still see
    flops, bytes_ = kv_work.decode_call(W, 2, 300, 4, ctx_window=200)
    assert flops == 200 * 4096
    assert bytes_ == 200 * 1024 + 2 * 4096
    # bytes bind it: 4 operations a byte of K and V against a v5e's 240
    assert flops / bytes_ < 8


def test_a_chunk_call_by_hand():
    # 3 tokens behind 10 cached keys see 3 x 10 + 6 pairs and 13 keys; 2
    # tokens behind none see 3 pairs and 2 keys
    flops, bytes_ = kv_work.chunk_call(W, (3, 2), (10, 0))
    assert flops == 39 * 4096
    assert bytes_ == 15 * 1024 + 5 * 4096
    # under a window of 4: 4 + 4 + 4 pairs over the last 6 keys; 1 + 2 over 2
    flops, bytes_ = kv_work.chunk_call(W, (3, 2), (10, 0), window=4)
    assert flops == 15 * 4096
    assert bytes_ == 8 * 1024 + 5 * 4096
    # a window nothing reaches is no window
    assert kv_work.chunk_call(W, (3, 2), (10, 0), window=64) \
        == kv_work.chunk_call(W, (3, 2), (10, 0))
    assert kv_work.chunk_call(W, (), ()) == (0.0, 0.0)


# --------------------------------------------------------------------------- #
# the readers
# --------------------------------------------------------------------------- #

STEP, PASS = "jit_serve_decode_step(1)", "jit_serve_paged_pass(3)"
SCOPES = "jit(serve)/jit(main)/while/body/closed_call/attn/"


def hand_view(config, records, call_us=1000.0):
    """One chip. Four executions of the decode step and four of the paged
    pass, a layer loop of two calls each (decode kernel ``call_us`` a call,
    chunk kernel 400 us); the trace's first and last execution (a step, a
    pass) are clipped and left out, so three of each are whole. ``records``: ``(name, t0_us, t1_us, args)`` on the
    capture's clock, which runs from 0 to 40 ms."""
    ops, modules = [], []
    for i in range(4):
        t = i * 5e6
        modules.append(Event(STEP, t, 4e6))
        ops += [Event(mosaic("closed_call.21"), t + j * 2e6, call_us * 1e3)
                for j in range(2)]
    for i in range(4):
        t = 20e6 + i * 5e6
        modules.append(Event(PASS, t, 4e6))
        ops += [Event(mosaic("closed_call.7"), t + j * 2e6, 400e3)
                for j in range(2)]
        # the decode rows that ride a pass: not the step's kernel time
        ops.append(Event(mosaic("closed_call.21"), t + 1e6, 50e3))
    kernel = {"closed_call.21": SCOPES + "attn_full/paged_decode_sidebuf/"
                                         "pallas_call",
              "closed_call.7": SCOPES + "attn_full/paged_chunk/pallas_call"}
    if "kv_lora_rank" in config:
        kernel["closed_call.21"] = SCOPES + "mla/decode/mla_decode/pallas_call"
    capture = Capture(
        trace_path="", start_ns=0.0, stop_ns=40e6,
        records=[("X", n, a * 1e3, b * 1e3, "lane", args, "thread")
                 for n, a, b, args in records])
    return {"trace": Trace(devices={0: DeviceTrace(ops=ops, modules=modules)}),
            "op_names": {STEP: dict(kernel), PASS: dict(kernel)},
            "capture": capture, "peaks": PEAKS, "config": config}


def llama(window=None, layers=2):
    return {"family": "llama", "num_hidden_layers": layers,
            "num_attention_heads": 8, "num_key_value_heads": 2,
            "hidden_size": 1024, "sliding_window": window,
            "engine": {"state_manager": {"max_context": 4096},
                       "kv_cache": {"block_size": 128}}}


STEPS = [("serve/decode/step", 100, 200,
          {"step": 0, "live": 2, "ctx": 300, "pages": 4, "ctx_window": 300}),
         ("serve/decode/step", 300, 400,
          {"step": 1, "live": 2, "ctx": 556, "pages": 6, "ctx_window": 400}),
         # began before the capture did: not whole inside it
         ("serve/decode/step", -50, 50,
          {"step": 9, "live": 2, "ctx": 9000, "pages": 90,
           "ctx_window": 512})]


def test_the_decode_share_by_hand():
    """Whole executions of two 1,000 us calls: 2,000 us a step. Two
    captured steps: 2 layers x (4 pages x 128 KiB + 8 KiB) and 2 x (6 x 128
    KiB + 8 KiB) bytes, 1,327,104 in the mean, 1,327.104 us at 1 GB/s."""
    reader = Registry().reader("paged.decode_roofline_share")
    view = hand_view(llama(), STEPS)
    assert reader(view) == pytest.approx(100 * 1327.104 / 2000.0)
    reading = paged().decode_reading(view)
    assert reading["executions"] == 3 and reading["records"] == 2
    assert reading["kernel_us"] == pytest.approx(2000.0)
    # every layer windowed at 256: 2 x (300 x 1 KiB + 8 KiB) and 2 x (400 x
    # 1 KiB + 8 KiB), 733,184 bytes in the mean
    assert reader(hand_view(llama(window=256), STEPS)) \
        == pytest.approx(100 * 733.184 / 2000.0)
    # a faster kernel reads higher
    assert reader(hand_view(llama(), STEPS, call_us=800.0)) \
        == pytest.approx(100 * 1327.104 / 1600.0)


def test_the_chunk_share_by_hand():
    """Whole executions of two 400 us calls: 800 us a pass. Its one
    paged pass: 39 pairs x 4,096 operations a layer, 159.744 us at the
    1 GFLOP/s these peaks give the chip (the bytes, 35.84 us, are the
    smaller bound), 319.488 us over the two layers."""
    passes = [("serve/prefill/pass", 500, 600,
               {"slots": 2, "tokens": 5, "kind": "paged", "ntok": (3, 2),
                "cached": (10, 0)}),
              ("serve/prefill/pass", 700, 800,
               {"slots": 1, "tokens": 9, "kind": "packed", "ntok": (9,),
                "cached": (0,)})]
    view = hand_view(llama(), passes)
    view["peaks"] = dict(PEAKS, bf16_flops_per_s=1e9)
    reader = Registry().reader("paged.chunk_roofline_share")
    assert reader(view) == pytest.approx(100 * 319.488 / 800.0)
    # a capture that holds no paged pass gives nothing, not 0
    view = hand_view(llama(), passes[1:])
    assert reader(view) is None
    # ... and neither does one whose only paged pass held no chunk
    view = hand_view(llama(), [("serve/prefill/pass", 500, 600, {
        "slots": 0, "tokens": 0, "kind": "paged", "ntok": (), "cached": ()})])
    assert reader(view) is None


def test_the_latent_share_by_hand():
    """Two layers of 600 us: 1,200 us a step. Two rows that hold 300 latent
    rows of 1,152 bytes between them, their queries and outputs 2 x 32 x
    (576 + 512) x 2 bytes: 484,864 bytes a layer, 969.728 us a step."""
    config = {"family": "joyai", "num_hidden_layers": 2,
              "num_attention_heads": 32, "kv_lora_rank": 512,
              "qk_rope_head_dim": 64}
    view = hand_view(config, STEPS[:1], call_us=600.0)
    assert mla_work.decode_call([300, 0], 32, 512, 64)[1] == 484864
    reader = Registry().reader("paged.mla_decode_roofline_share")
    assert reader(view) == pytest.approx(100 * 969.728 / 1200.0)
    # the K/V readers find no K/V layout there, the latent one none here
    assert paged().decode_roofline_share(view) is None
    assert paged().chunk_roofline_share(view) is None
    assert reader(hand_view(llama(), STEPS)) is None


READERS = ("decode_roofline_share", "chunk_roofline_share",
           "mla_decode_roofline_share")


@pytest.mark.parametrize("reader", READERS)
def test_a_reader_says_nothing_where_there_is_nothing_to_read(reader):
    """No capture (``--trace 0`` and ``1``); the recorded tiny trace, which
    holds no such kernel; and a program older than the arguments — the
    parent's records carry ``live`` and ``slots``, ``tokens``, ``kind``
    alone. None raises."""
    read = getattr(paged(), reader)
    configs = [llama(), Registry().config(LATENT)]
    for config in configs:
        assert read({"config": config, "peaks": PEAKS, "trace": None}) is None
        tiny = {"trace": xplane.load(SCOPED, ANNOTATIONS),
                "op_names": hlo_names.load(SCOPED),
                "capture": kept_capture(), "peaks": PEAKS, "config": config}
        assert read(tiny) is None
        old = hand_view(
            dict(config, num_hidden_layers=2),
            [("serve/decode/step", 100, 200, {"step": 0, "live": 2}),
             ("serve/prefill/pass", 500, 600,
              {"slots": 2, "tokens": 5, "kind": "paged"})])
        assert read(old) is None
    # a windowed model whose records lack the window's sum
    bare = [(n, a, b, {k: v for k, v in args.items() if k != "ctx_window"})
            for n, a, b, args in STEPS]
    if reader == "decode_roofline_share":
        assert read(hand_view(llama(window=256), bare)) is None
        assert read(hand_view(llama(), bare)) is not None


def test_the_four_entries_and_their_cells():
    """Entries of ``per_layer``, each cell of a list reports what the entry
    moves, and every K/V serving cell has the decode kernel's share. The
    chunk kernel's is listed only where every capture holds a paged pass (a
    line that lacks a listed metric is refused): among the decode share's
    cells, and one whose arrivals do not wait on the replica."""
    names = ("paged_decode_roofline_share.serve",
             "paged_decode_roofline_share.steady",
             "paged_chunk_roofline_share.serve",
             "mla_decode_roofline_share.assist")
    # by name, not by place: a later PR appends after them
    entries = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in names}
    assert len(entries) == 4
    assert all(m["unit"] == "%" and m["better"] == "higher"
               and m["source"] == "device_trace" for m in entries.values())
    cells = {w["name"]: w["config"] for w in BENCH["workloads"]}
    kv_cells = {c for c, cfg in cells.items() if cfg in LAYERS}
    decode = set(entries["paged_decode_roofline_share.serve"]["workloads"]) \
        | set(entries["paged_decode_roofline_share.steady"]["workloads"])
    assert decode == kv_cells
    chunk = entries["paged_chunk_roofline_share.serve"]["workloads"]
    assert chunk and set(chunk) \
        <= set(entries["paged_decode_roofline_share.serve"]["workloads"])
    assert all(Registry().cell(c)["driver"] == "serve_open"
               for c in chunk)
    assert [cells[c] for c in entries["mla_decode_roofline_share.assist"][
        "workloads"]] == [LATENT]
