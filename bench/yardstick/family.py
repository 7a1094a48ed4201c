"""The seam between a configuration file and the numbers read from it.

Each architecture family is one module, ``bench/families/<family>.py``,
found by the ``"family"`` key of the configuration file, the way a
metric's reader is found by its name. Nothing else in the harness knows
an architecture. A family module provides:

- ``dims(conf)``: its sizes, read from the file's published keys alone;
  it refuses a key it does not cover. The result is hashable and has at
  least ``layers``, ``vocab`` and ``kv_bytes_per_token`` (bytes of cache
  one token keeps over all layers).
- ``program_config(conf, dims)``: the program's ``ModelConfig`` for the
  file, checked field by field against it.
- ``make_params(dims, seed, dtype)``: weights drawn from the seed on the
  device, laid out as the program's parameter tree.
- ``served_gaps(params, dims, prompt, output, *, control=False)``: the
  plain float32 reference teacher-forced over prompt and served tokens:
  per served token, the reference's best logit minus its logit for that
  token, and with ``control`` the same for the token its float8 control
  puts first there; ``(gaps, control gaps or None)``.
- ``prefill_flops(dims, isl)``, ``decode_flops(dims, context)`` and
  ``decode_step_bytes(dims, contexts)``: operations of one prompt and of
  one decoded token, and the least bytes of one decode step over the
  contexts of all its requests, at true lengths.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from typing import Dict

FAMILIES = Path(__file__).resolve().parents[1] / "families"
INTERFACE = ("dims", "program_config", "make_params", "served_gaps",
             "prefill_flops", "decode_flops", "decode_step_bytes")


def load_family(name: str):
    """The module of family ``name``, checked for the whole interface."""
    path = FAMILIES / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no architecture family {name!r}: add "
                         f"bench/families/{name}.py (see "
                         f"bench/yardstick/family.py for what it provides)")
    spec = importlib.util.spec_from_file_location(f"bench_family_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    # dataclasses look their module up while the class is made
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    missing = [f for f in INTERFACE if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"bench/families/{name}.py lacks {missing}")
    return mod


def family_of(conf: Dict):
    """The family module a configuration file names."""
    if "family" not in conf:
        raise ValueError("the configuration file names no \"family\"; "
                         "one of bench/families/<family>.py")
    return load_family(conf["family"])


def key_for(seed: int):
    """A jax PRNG key for a seed of any size."""
    import jax
    key = jax.random.PRNGKey(seed % (1 << 32))
    return jax.random.fold_in(key, (seed >> 32) % (1 << 31))
