"""One module per architecture, found by the configuration file's
``model_type``: ``bench/arch/<model_type>.py``.

A module gives what the harness needs of a model that the generic files
cannot know:

- ``dims(config) -> dict``: the model's dimensions from its
  configuration file.  Every value is hashable (a scalar, or a tuple for
  a per-layer pattern): the reference caches its programs by them.
- ``shapes(dims) -> dict``: the weight tree as the program takes it,
  each leaf ``(shape, dtype, init std or "norm")`` (``bench/weights.py``).
- ``program_config(name, dims)``: the program's configuration object.
  The only function that imports the program.
- ``forward(dims, mm) -> fwd(params, tokens, rows) -> logits``: the
  plain float32 forward over one sequence, every matmul through
  ``mm(spec, a, b)`` (``bench/reference.py`` rounds its operands).
- the counts of ``bench/flops.py``: ``token_flops``,
  ``prefill_chunk_flops``, ``attn_flops``, ``kv_read_bytes``,
  ``step_weight_bytes``, and what else its callers read.

A new architecture is that module and its configuration file; no other
file of the harness changes.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

ARCH_DIR = Path(__file__).resolve().parent


def load(model_type: str):
    """The module ``ARCH_DIR / f"{model_type}.py"``."""
    if not str(model_type).isidentifier():
        raise ValueError(f"model_type {model_type!r} is not a module name")
    path = ARCH_DIR / f"{model_type}.py"
    if not path.is_file():
        raise FileNotFoundError(f"model_type {model_type!r} has no "
                                f"architecture module at {path}")
    return _module(path)


@functools.lru_cache(maxsize=None)
def _module(path: Path):
    # loaded once per file: the counts are called once per served token
    spec = importlib.util.spec_from_file_location(
        f"bench.arch.{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
