"""Architecture modules, found by name.

Everything the benchmark knows of one model architecture sits in one file,
``bench/arch/<name>.py``, and a configuration names it under
``architecture.module``. The shared code (weights, budget, reference,
FLOP counts, readers) reaches the model only through that module, so an
architecture joins the benchmark as new files. A module gives:

- weights: ``shapes(cfg)`` (name -> shape of every tensor, in the
  benchmark's own layout) and ``init(name, shape, key, dtype)``;
- the program: ``model_config(cfg)`` and ``program_params(cfg, w)``, the
  program's ``ModelConfig`` and the same arrays in its parameter tree;
- bytes: ``layer_bytes(cfg)``, one dict per layer with ``fp`` and ``q``
  (its weights at the served dtype and with its matrices int4) and ``kv``
  (its share of one paged KV block), and ``misc_bytes(cfg)`` (embedding,
  head, final norm);
- the reference: ``n_layers(cfg)``; ``layer_weights(w, i)``;
  ``matrices(lw)``, the names of a layer's weight matrices (``(..., K,
  N)``, the ones a swap quantizes when large enough and the control rounds);
  ``ref_globals(w)`` -> (embedding table (V, D), head (D, V), final-norm
  weights or None); ``ref_static(cfg)``, a hashable tuple of what the
  jitted functions need; ``ref_store(static, T)``, one layer's empty KV
  store for ``T`` positions; ``ref_layer(lw, x, store, qmask, start, lo,
  hi, *, static, control)`` -> (x, store); ``ref_read(x, final, head,
  tokens, *, static, control)`` -> (best logit, best token, logit of
  ``tokens``) per row;
- counts: ``touched_params(cfg)`` (parameters one token multiplies through
  in all layers), ``head_params(cfg)``, ``attn_flops(cfg, n_query,
  ctx_before)`` (all layers), ``kv_read_bytes(cfg, quantized)`` (one KV
  block of all layers at its stored precision), ``decode_io_bytes(cfg)``
  and ``chunk_io_bytes(cfg)`` (what attention reads and writes besides the
  stored blocks, per decoded row and per prefill token, all layers).
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def modules(root: Path = ROOT) -> list:
    """The architecture modules under ``root``."""
    return sorted(p.stem for p in (root / "bench" / "arch").glob("*.py")
                  if not p.stem.startswith("_"))


@functools.lru_cache(maxsize=None)
def _load(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_arch_" + Path(path).stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bind(cfg: dict, root: Path = ROOT) -> dict:
    """``cfg`` with the architecture module it names under the key
    ``arch``. A missing or unknown name is an error that lists the modules
    there; there is no default."""
    name = cfg.get("architecture", {}).get("module")
    known = modules(root)
    if name not in known:
        raise KeyError(f"configuration {cfg.get('name')!r} names architecture "
                       f"module {name!r}; bench/arch has {known}")
    return dict(cfg, arch=_load(str(root / "bench" / "arch" / f"{name}.py")))
