"""One module per model family, named by the ``reference`` key of a
configuration file: the family's single home in the benchmark.

Besides ``logits`` (the plain float32 reference), a family module states:

- ``LAYER``, ``TOP``: its leaves as (name, init, scale), in the order that
  ``bench.weights`` folds their index into the seed's key;
- ``layer_shapes(m)``, ``top_shapes(m, vocab_rows)``: each leaf's shape
  from the ``model`` block;
- ``program_fields(m)``: the fields of the program's ``ModelConfig`` that
  have to equal the file's;
- ``layer_matmul_params(m)``, ``layer_params(m)``,
  ``decode_weight_bytes(m, batch)``: its least work, which ``bench.flops``
  reads.

Shared code finds the module through :func:`family`, from a ``model`` block
that carries the configuration's ``reference`` name (``Cell.model``).
"""

from __future__ import annotations

import importlib


def family(m: dict):
    """The family module of a ``model`` block that names its reference."""
    return importlib.import_module(f"{__name__}.{m['reference']}")
