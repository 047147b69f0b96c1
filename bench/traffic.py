"""The one traffic generator: reads a mix's data file and makes its
requests from the seed.

A mix is a JSON file under ``bench/traffic/``.  Keys:

- ``loop``: ``"closed"`` (the only kind so far): one batch of ``batch``
  requests at a time, the next one due when the last token of the one
  before reaches the host;
- ``batch``, ``prompt_len``, ``output_len``: fixed shapes (the server
  batches fixed shapes only);
- ``prompt_ids``: ``"uniform"``, token ids drawn uniformly from the vocab.

Batch ``n`` of seed ``s`` is the same wherever it is asked for: the window
makes it to serve, and the check makes it again to compare.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

KINDS = {"closed"}
IDS = {"uniform"}


@dataclass(frozen=True)
class Mix:
    name: str
    batch: int
    prompt_len: int
    output_len: int

    def prompts(self, seed: int, n: int, vocab: int) -> np.ndarray:
        """Prompt ids [batch, prompt_len] of batch ``n``."""
        rng = np.random.default_rng([seed, 1, n])
        return rng.integers(0, vocab, (self.batch, self.prompt_len), dtype=np.int32)


def load(path: Path) -> Mix:
    spec = json.loads(Path(path).read_text())
    if spec.get("loop") not in KINDS or spec.get("prompt_ids") not in IDS:
        raise ValueError(f"{path}: unsupported loop {spec.get('loop')!r} or "
                         f"prompt_ids {spec.get('prompt_ids')!r}")
    mix = Mix(Path(path).stem, int(spec["batch"]), int(spec["prompt_len"]),
              int(spec["output_len"]))
    if min(mix.batch, mix.prompt_len, mix.output_len) < 1:
        raise ValueError(f"{path}: sizes must be positive")
    return mix
