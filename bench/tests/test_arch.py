"""The architecture seam: a configuration names its module under
``architecture.module`` and the shared code reaches the model only through
it. The dense module reproduces the counts and the reference logits the
harness gave before the seam; a new architecture joins by new files alone;
an unknown name fails; and OLMo-1B's file gives the program's own preset
apart from what the file says it changed."""
import dataclasses
import json
import os
import shutil
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import run as R  # noqa: E402
from bench.lib import arch, flops, reference, system, weights  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
V5E_BYTES_LIMIT = 16_909_336_064


def cfg(name, root=ROOT):
    return arch.bind(json.loads((root / "bench" / "configs" / f"{name}.json")
                                .read_text()), root)


# --- the dense module keeps the numbers the harness had -------------------
def test_qwen_counts_are_pinned():
    """Qwen2-1.5B's pool, FLOPs and attention costs, as the harness counted
    them before the architecture seam."""
    c = cfg("qwen2-1.5b")
    assert system.size_budget(c, V5E_BYTES_LIMIT) == {
        "hbm_budget_bytes": 13211120911, "start_blocks": 20628,
        "capacity": 20629, "block_bytes": 458752,
        "device_bytes": 13761475174}
    assert flops.matmul_params(c) == 1543569408
    assert flops.step_flops(c, [100, 700, 2047], [(512, 256), (0, 64)],
                            5) == 877776027648
    assert flops.decode_attn_cost(c, [(100, 2), (1500, 0), (33, 3)]) == (
        281444352, 47167680)
    assert flops.chunk_attn_cost(
        c, [(512, 256, 0), (0, 64, 0), (1024, 128, 5)]) == (
        52534444032, 132810944)


def test_olmo_pool_is_sized_to_the_chip():
    """OLMo-1B under the morph policy: 5,151 blocks of 16 tokens at most
    (82k tokens), 4,414 at level 0, one power-of-two bucket."""
    s = system.size_budget(cfg("olmo-1b"), V5E_BYTES_LIMIT)
    assert (s["capacity"], s["start_blocks"], s["block_bytes"]) == (
        5151, 4414, 2097152)


TINY = {"name": "tiny", "source": "test",
        "hidden_size": 128, "intermediate_size": 256,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 512,
        "rope_theta": 1000000.0, "hidden_act": "silu",
        "tie_word_embeddings": True, "rms_norm_eps": 1e-05,
        "architecture": {"module": "dense", "norm": "rmsnorm",
                         "qkv_bias": True},
        "serving": {"swap_group": 128, "kv_block_size": 16}}
TINY_OLMO = {k: v for k, v in TINY.items() if k != "rms_norm_eps"} | {
    "num_key_value_heads": 4, "tie_word_embeddings": False,
    "rope_theta": 10000.0, "layer_norm_eps": 1e-05,
    "architecture": {"module": "dense", "norm": "nonparam_ln",
                     "qkv_bias": False}}
# (best logit, best token, logit of the wanted token) at positions 5, 19
# (level 0) and 20, 30, 39 (level 1, block 0 held int8), read from the
# reference before the seam
PINNED = {
    "qwen": [(0.5941868424415588, 7, -0.32705774903297424),
             (0.8074067234992981, 129, 0.1244606301188469),
             (0.8159663677215576, 456, -0.2715455889701843),
             (0.6971609592437744, 241, -0.05859876796603203),
             (0.6958920955657959, 348, 0.19289183616638184)],
    "olmo": [(3.2463836669921875, 364, -2.4147183895111084),
             (3.52685809135437, 80, 0.5313227772712708),
             (2.669034242630005, 366, -0.3856510818004608),
             (3.8501100540161133, 491, 0.6396592855453491),
             (2.7953879833221436, 506, -0.12613201141357422)]}


@pytest.mark.parametrize("name,c", [("qwen", TINY), ("olmo", TINY_OLMO)])
def test_reference_logits_are_pinned(name, c):
    c = arch.bind(c)
    toks = [int(x) for x in np.random.default_rng(5).integers(0, 512, 40)]
    segs = [(0, 0, 20, frozenset()), (1, 20, 40, frozenset({0}))]
    want = {p: toks[p + 1] if p + 1 < 40 else 0 for p in (5, 19, 20, 30, 39)}
    w = weights.make(c, 2**33 + 3)
    with jax.default_matmul_precision("highest"):
        out = reference.Reference(c, w, "exact", 64).replay(
            toks, segs, want.keys(), want)
    for p, (best, tok, at) in zip(sorted(want), PINNED[name]):
        assert out[p][1] == tok
        assert out[p][0] == pytest.approx(best, rel=1e-5, abs=1e-6)
        assert out[p][2] == pytest.approx(at, rel=1e-5, abs=1e-6)


# --- an architecture joins by new files alone -----------------------------
TOY = '''"""A toy architecture: each layer adds tanh(x @ mix) to the stream."""
import functools

import jax
import jax.numpy as jnp


def shapes(cfg):
    L, D, V = (cfg[k] for k in ("num_hidden_layers", "hidden_size",
                                "vocab_size"))
    return {"emb": (V, D), "mix": (L, D, D)}


def init(name, shape, key, dtype):
    return (0.3 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def layer_bytes(cfg):
    D = cfg["hidden_size"]
    return [{"fp": 2 * D * D, "q": D * D // 2, "kv": 4096}] * \\
        cfg["num_hidden_layers"]


def misc_bytes(cfg):
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]


def n_layers(cfg):
    return cfg["num_hidden_layers"]


def layer_weights(w, i):
    return {"mix": w["mix"][i]}


def matrices(lw):
    return ("mix",)


def ref_globals(w):
    return w["emb"], w["emb"].T, None


def ref_static(cfg):
    return (cfg["hidden_size"],)


def ref_store(static, T):
    return jnp.zeros((T,), jnp.float32)


@functools.partial(jax.jit, static_argnames=("static", "control"))
def ref_layer(lw, x, store, qmask, start, lo, hi, *, static, control):
    return x + jnp.tanh(x @ lw["mix"].astype(jnp.float32)), store


@functools.partial(jax.jit, static_argnames=("static", "control"))
def ref_read(x, final, head, tokens, *, static, control):
    logits = x @ head.astype(jnp.float32)
    at = jnp.take_along_axis(logits, tokens[:, None], 1)[:, 0]
    return logits.max(-1), jnp.argmax(logits, -1), at


def touched_params(cfg):
    return cfg["num_hidden_layers"] * cfg["hidden_size"] ** 2


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def attn_flops(cfg, n_query, ctx_before):
    return 3 * n_query * ctx_before


def kv_read_bytes(cfg, quantized):
    return 1000 if quantized else 2000


def decode_io_bytes(cfg):
    return 7


def chunk_io_bytes(cfg):
    return 5
'''


def test_a_new_architecture_joins_by_new_files_alone(tmp_path):
    """A toy module, its configuration, a cell and a mix, added as files
    beside an unchanged copy of the benchmark, are found through the root
    ``load_spec`` takes, and the shared weights, budget, counts and
    reference run on it."""
    root = tmp_path / "tree"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "arch" / "toy.py").write_text(TOY)
    toy = {"name": "toy", "source": "test", "hidden_size": 128,
           "num_hidden_layers": 3, "vocab_size": 64,
           "architecture": {"module": "toy"},
           "serving": {"swap_group": 128, "kv_block_size": 16,
                       "policy": "static_fp16", "kv_quant": False,
                       "headroom_bytes": 0, "step_temp_share": 0.0}}
    (root / "bench" / "configs" / "toy.json").write_text(json.dumps(toy))
    (root / "bench" / "cells" / "toy.chat.json").write_text("{}")
    shutil.copy(ROOT / "bench" / "traffic" / "chat.json",
                root / "bench" / "traffic" / "chat.json")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": "test",
                             "file": "bench/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.chat", "config": "toy",
                               "traffic": "chat", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    c = R.load_spec("toy.chat", root)["cfg"]
    assert c["arch"].__file__ == str(root / "bench" / "arch" / "toy.py")
    L, D, V = 3, 128, 64
    assert flops.step_flops(c, [10], [(0, 4)], 2) == (
        2 * L * D * D * 5 + 2 * D * V * 2 + 3 * 10 + 3 * 4 * 0)
    # 33 tokens of context: 3 blocks, one of them quantized
    assert flops.decode_attn_cost(c, [(33, 1)]) == (3 * 33,
                                                    2 * 2000 + 1000 + 7)
    assert flops.chunk_attn_cost(c, [(16, 8, 0)]) == (3 * 8 * 16,
                                                      2000 + 8 * 5)
    s = system.size_budget(c, 10_000_000)
    assert s["block_bytes"] == L * 4096

    w = weights.make(c, 7, dtype=jnp.float32)
    assert {k: v.shape for k, v in w.items()} == {"emb": (V, D),
                                                  "mix": (L, D, D)}
    toks = [1, 5, 9, 2, 7]
    with jax.default_matmul_precision("highest"):
        got = reference.Reference(c, w, "exact", 16).replay(
            toks, [(0, 0, 5, frozenset())], [4], {4: 3})
    x = np.asarray(w["emb"], np.float64)[toks[4]]
    for i in range(L):
        x = x + np.tanh(x @ np.asarray(w["mix"][i], np.float64))
    logits = x @ np.asarray(w["emb"], np.float64).T
    best, tok, at = got[4]
    assert tok == int(np.argmax(logits))
    assert best == pytest.approx(logits.max(), rel=1e-4)
    assert at == pytest.approx(logits[3], rel=1e-4, abs=1e-4)


@pytest.mark.parametrize("module", ["no_such_module", None])
def test_an_unknown_or_missing_module_fails(module):
    c = {"name": "x", "architecture": {"module": module}}
    with pytest.raises(KeyError, match="dense"):
        arch.bind(c)


# --- OLMo-1B is the program's own preset ----------------------------------
def test_olmo_file_gives_the_programs_preset():
    """``olmo-1b.json`` gives the program's ``OLMO_1B`` apart from what the
    file lists under ``changed`` (and its provenance string)."""
    from repro.configs.archs import OLMO_1B
    c = cfg("olmo-1b")
    mc = c["arch"].model_config(c)
    field = {"tie_word_embeddings": "tie_embeddings"}
    changed = {field[k] for k in c["changed"]}
    assert changed == {"tie_embeddings"}
    assert mc.resolved_head_dim == OLMO_1B.resolved_head_dim
    skip = changed | {"source", "head_dim"}
    got = {k: v for k, v in dataclasses.asdict(mc).items() if k not in skip}
    want = {k: v for k, v in dataclasses.asdict(OLMO_1B).items()
            if k not in skip}
    assert got == want
    assert mc.tie_embeddings and not OLMO_1B.tie_embeddings
