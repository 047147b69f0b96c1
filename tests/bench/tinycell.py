"""A benchmark root with tiny cells of the program's smoke configurations,
for driving the harness on the CPU.  Not a test module."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the program's smoke configurations (repro.configs ... smoke()), each with
# its family's reference module
TINY = {
    "tiny-qwen": {"program_config": "qwen1_5_4b", "reference": "dense", "model": {
        "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "head_dim": 16,
        "d_ff": 160, "vocab_size": 128, "rope": "default", "rope_theta": 5000000.0,
        "norm_eps": 1e-06, "qkv_bias": True, "tie_embeddings": False, "dtype": "bfloat16"}},
    "tiny-chatglm": {"program_config": "chatglm3_6b", "reference": "dense", "model": {
        "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
        "d_ff": 160, "vocab_size": 128, "rope": "half", "rope_theta": 10000.0,
        "norm_eps": 1e-06, "qkv_bias": True, "tie_embeddings": False, "dtype": "bfloat16"}},
}
MIXES = {
    "tiny_decode": {"batch": 4, "prompt_len": 24, "output_len": 12},
    "tiny_prefill": {"batch": 2, "prompt_len": 32, "output_len": 3},
}


def make_root(tmp: Path, limit: float | None = 1.0, sample: int = 4,
              configs: dict = TINY) -> Path:
    """Write BENCHMARK.json and the cells' files under ``tmp``: one cell per
    (tiny configuration, mix), named ``<config>.<mix>``, with a copy of the
    repository's metric readers.  ``configs``: {name: {"program_config",
    "reference", "model"}}, smoke presets of the program."""
    bench = tmp / "bench"
    for sub in ("configs", "traffic", "cells"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(REPO / "bench" / "metrics", bench / "metrics", dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    manifest["configs"], manifest["workloads"] = [], []
    for name, spec in configs.items():
        (bench / "configs" / f"{name}.json").write_text(json.dumps(
            {**spec, "program_preset": "smoke", "model_from": {}}))
        manifest["configs"].append({"name": name, "source": "smoke()", "reduced": [],
                                    "file": f"bench/configs/{name}.json", "why": "tests"})
        for mix, sizes in MIXES.items():
            (bench / "traffic" / f"{mix}.json").write_text(json.dumps(
                {"loop": "closed", "prompt_ids": "uniform", **sizes}))
            cell = f"{name}.{mix}"
            (bench / "cells" / f"{cell}.json").write_text(json.dumps(
                {"sample_requests": sample, "reference_rows": 1,
                 "limits": {"logit_gap": limit}}))
            manifest["workloads"].append({"name": cell, "config": name, "traffic": mix,
                                          "chips": 1, "why": "tests"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    shutil.copy(REPO / "bench" / "peaks.json", bench / "peaks.json")
    return tmp


CACHE_OPTIONS = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")


def isolated_cache_fixture(tmp_path_factory):
    """Module fixture body: the harness's compile cache goes to a temporary
    directory, and JAX's cache options are put back afterwards."""
    import jax
    import pytest

    prev = {k: getattr(jax.config, k) for k in CACHE_OPTIONS}
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path_factory.mktemp("jax_cache")))
    yield
    mp.undo()
    for k, v in prev.items():
        jax.config.update(k, v)
