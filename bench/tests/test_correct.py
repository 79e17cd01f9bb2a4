"""The comparison that decides ``correct``, at a size a CPU test run holds.

A two-layer model at the configurations' layout (GQA, QKV biases, tied
head) is served through the harness's whole run -- weights from the seed,
the engine under the morph policy with its int8 KV tier on and a tight
budget, every step program compiled up front, the open-loop window, the
reference after it -- without the look for a chip. A sound run must come
out correct; a run with the lower-precision control in the program's place
must not; nor must a run whose tokens are altered where the engine
produces them.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

from bench import run as R  # noqa: E402
from bench.lib import arch  # noqa: E402

CFG = {"name": "tiny", "source": "test",
       "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
       "vocab_size": 512, "rope_theta": 1000000.0, "hidden_act": "silu",
       "tie_word_embeddings": True,
       "rms_norm_eps": 1e-05,
       "architecture": {"module": "dense", "norm": "rmsnorm",
                        "qkv_bias": True},
       "serving": {"dtype": "bfloat16", "policy": "morph",
                   "mode": "accuracy", "swap_bits": 4, "swap_group": 128,
                   "kv_quant_bits": 8,
                   "kv_block_size": 16, "max_tokens_per_step": 64,
                   "headroom_bytes": 0, "kv_quant_step_temp_share": 0.3,
                   "step_temp_share": 0.05,
                   "swap_levels": [0, 1], "decode_nb_bucketing": False,
                   "kv_quant": True,
                   "min_chunk_tokens": 64}}
CFG = arch.bind(CFG)
MIX = {"shape_seed": 1,
       "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 60},
       "output": {"median": 12, "sigma": 0.3, "min": 6, "max": 20}}
# read on the CPU at this size: sound runs' widest gap 0-0.018, the control
# in the program's place at the served positions 0.058-0.092, runs with
# altered tokens 0.89-1.07
LIMIT = 0.03
CELL = {"rate_rps": 3.0, "preroll_s": 2.0, "warm_s": 0.0, "max_batch_slots": 8,
        "max_seq_len": 96, "ttft_limit_s": 2.0, "gap_limit_ms": 100.0,
        "check_tokens": 60, "check_requests": 4, "correct_gap_limit": LIMIT,
        "bytes_limit": 3_300_000}
SPEC = {"workload": {"name": "tiny", "chips": 1}, "cfg": CFG, "cell": CELL,
        "mix": MIX, "per_layer": [],
        "end_to_end": [{"name": n, "unit": "x"} for n in (
            "itl_p99_ms", "output_tok_per_s", "setup_s")]}


def run(seed, **kw):
    return R.run_cell(SPEC, seed, 12.0, False, device=jax.devices()[0],
                      **kw)


@pytest.mark.parametrize("control", [False, True])
def test_sound_run_is_correct_and_control_is_not(control):
    """The program's own tokens pass; the control, put in its place at the
    same served positions, does not."""
    res = run(11, control=control)
    assert res["correct"] is not control, res["checks"]
    gap = res["checks"]["logit_gap"]["value"]
    assert gap > LIMIT if control else gap <= LIMIT


def test_altered_token_is_not_correct():
    def tamper(eng):
        real = eng._decode_real

        def decode(run_):
            real(run_)
            for r in run_:
                if len(r.generated) % 3 == 0:
                    r.generated[-1] = (r.generated[-1] + 1) % CFG["vocab_size"]
        eng._decode_real = decode
    res = run(12, tamper=tamper)
    assert not res["correct"], res["checks"]


def pin_level_1(eng):
    """Serve every step at swap level 1: layer 0 on its int4 weights."""
    eng._pinned_level = 1
    eng.actuator.level = 1
    eng.controller.commit(1)


@pytest.mark.parametrize("control", [False, True])
def test_swapped_level_sound_is_correct_and_control_is_not(control, capsys):
    """At a swapped level the program's int4 tokens pass; the control, whose
    int4 has one scale per output column instead of one per group of 128,
    does not. Read on the CPU at this size, seeds 13-15: the program
    0-0.0063, the control 0.040-0.068."""
    res = run(13, control=control, tamper=pin_level_1)
    assert "levels [1]," in capsys.readouterr().err
    assert res["correct"] is not control, res["checks"]
