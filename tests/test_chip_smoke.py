"""``chip_smoke.py`` off the chip: its serving and parity phases at
reduced size in Pallas interpret mode, its four-replica phase on four
virtual CPU devices, and its refusal to run without a TPU."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.configs.archs import QWEN2_1P5B, reduced
from repro.kernels import dispatch
from repro.models import lm

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serving_phase_reduced_pallas_interpret():
    """The serving phase's checks pass at reduced size through the Pallas
    kernel bodies: every request finishes with its token count, and the
    run swaps a layer, quantizes cold KV and chunks a long prompt. The
    traffic is a quarter of the chip run's and the virtual clock of a
    reduced model is fast, so the controller's window shrinks with it."""
    smoke = _load_smoke()
    cfg = reduced(QWEN2_1P5B)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    requests = tuple((max(p // 4, 8), max(g // 4, 4))
                     for p, g in smoke.SERVE_REQUESTS)
    prev = dispatch.set_mode("pallas_interpret")
    try:
        res = smoke.serve_phase(cfg, params, requests=requests,
                                step_tokens=64, monitor_window_s=5e-4,
                                log=lambda _: None)
    finally:
        dispatch.set_mode(prev)
    assert res["finished"] == len(requests)
    assert res["swaps"] >= 1 and res["kv_quant_events"] >= 1
    assert res["chunk_steps"] >= 1


def test_parity_phase_reduced_pallas_interpret():
    """The logits through the Pallas kernel bodies agree with the xla path
    within the smoke's tolerance."""
    smoke = _load_smoke()
    cfg = reduced(QWEN2_1P5B)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    prev = dispatch.set_mode("pallas_interpret")
    try:
        res = smoke.parity_phase(cfg, params,
                                 lm.params_to_layer_list(cfg, params),
                                 chunk=64, fault_layers=(),
                                 log=lambda _: None)
    finally:
        dispatch.set_mode(prev)
    assert res["relative"] <= smoke.PARITY_REL_TOL


def test_kernel_parity_separates_planted_faults():
    """Each kernel agrees with the xla path within KERNEL_REL_TOL, and a
    wrong kv head, a wrong table entry or a dropped dequant term in the
    walk (and a shifted scale column in the GEMM) reads above it."""
    smoke = _load_smoke()
    prev = dispatch.set_mode("pallas_interpret")
    try:
        res = smoke.kernel_parity_phase(reduced(QWEN2_1P5B), slots=4,
                                        chunk=64, log=lambda _: None)
    finally:
        dispatch.set_mode(prev)
    assert max(res["relative"].values()) <= smoke.KERNEL_REL_TOL
    assert len(res["faults"]) == 2 * len(smoke.PARITY_FAULTS) + 1
    assert min(res["faults"].values()) > smoke.KERNEL_REL_TOL


def test_cluster_phase_on_four_cpu_devices():
    """The four-replica path on four virtual CPU devices: each replica's
    arrays on its own device, a drain that migrates KV to a peer, and
    token streams equal to one replica's."""
    code = ("import sys; sys.path.insert(0, 'src'); import chip_smoke; "
            "from repro.configs.archs import QWEN2_1P5B, reduced; "
            "r = chip_smoke.cluster_phase(0, cfg=reduced(QWEN2_1P5B), "
            "budget_bytes=64 * 2**20); "
            "assert r['migrations_ok'] >= 1 and r['streams_equal'] == 8")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "streams equal to the one-replica run: 8/8" in out.stdout


def test_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode != 0
    assert "no TPU present" in out.stderr
    assert '"ok"' not in out.stdout
