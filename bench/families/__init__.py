"""Layer families, one module each, found by the ``family`` key of a
configuration file (``dense`` where it names none).

A family module does everything that depends on the model's layers:

- ``Dims``: a frozen, hashable dataclass (a static jit argument) with
  ``from_config(conf)`` and at least ``vocab``;
- ``program_config(conf, dims)``: the program's ``ArchConfig`` at the
  benchmark's widths and equations, or ``BenchError`` where the program's
  registered config is not of this family;
- ``program_params(dims, root)``: every weight in the program's layout,
  drawn from the key ``root``;
- ``served_gaps(dims, seed, served, *, control=False)``: the plain float32
  reference and its lower-precision control, as ``bench/reference.py``.

and, where it can, the counts that per-layer readers need: ``token_flops``,
``prefill_chunk``, ``decode_step``, ``weight_bytes``,
``kv_bytes_per_token``, ``rmsnorm_bytes`` (``bench/flops.py`` gives their
arguments).  A reader whose count the family lacks reads nothing.
"""

import importlib

from bench.weights import root_key  # noqa: F401  (the seed's key, any family)

REQUIRED = ("Dims", "program_config", "program_params", "served_gaps")
COUNTS = ("token_flops", "prefill_chunk", "decode_step", "weight_bytes",
          "kv_bytes_per_token", "rmsnorm_bytes")


def of(conf: dict):
    """The family module of configuration ``conf``."""
    from bench.harness import BenchError

    name = conf.get("family", "dense")
    path = f"bench.families.{name}"
    try:
        mod = importlib.import_module(path)
    except ModuleNotFoundError as exc:
        if exc.name != path:
            raise
        raise BenchError(f"no family module {path}") from None
    missing = [n for n in REQUIRED if not hasattr(mod, n)]
    if missing:
        raise BenchError(f"family {name} lacks {', '.join(missing)}")
    return mod

