"""A configuration finds its architecture binding by `model_type`.

A new architecture is new files only: its binding under `bench/arch`, its
configuration and an entry in `BENCHMARK.json`.  The harness finds them
under the root it is given, and an unknown `model_type` is an error that
names the file looked for, never a fallback to another architecture.
"""

from __future__ import annotations

import json
import os
import shutil

import pytest
import tinybench

from bench import arch
from bench.lib import spec


def test_unknown_model_type_names_the_file_it_wanted():
    with pytest.raises(FileNotFoundError, match="bench/arch/no_such_arch"):
        arch.for_config(tinybench.config(model_type="no_such_arch"))
    cfg = tinybench.config()
    del cfg["model_type"]
    with pytest.raises(KeyError, match="model_type"):
        arch.for_config(cfg)


def test_every_binding_provides_the_interface():
    assert "qwen3" in arch.names()
    for t in arch.names():
        b = arch.binding(t)
        assert all(callable(getattr(b, p)) for p in arch.PROVIDES if p != "TINY")
        assert {"config", "cases", "atol", "why"} <= set(b.TINY)
        assert b.TINY["config"]["model_type"] == t


def _tree(root):
    """Every file under `root`'s benchmark, with its size and mtime."""
    out = {}
    for base in ("bench", "tests/bench"):
        for d, dirs, files in os.walk(os.path.join(root, base)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                st = os.stat(os.path.join(d, f))
                out[os.path.join(d, f)] = (st.st_size, st.st_mtime_ns)
    st = os.stat(os.path.join(root, "BENCHMARK.json"))
    out["BENCHMARK.json"] = (st.st_size, st.st_mtime_ns)
    return out


def test_a_new_architecture_is_new_files_only(tmp_path):
    """A second binding (here one that re-exports Qwen3 under another
    `model_type`), a configuration that names it and a cell, all in a root
    of their own, are found by the loaders with no file of the harness
    edited."""
    before = _tree(tinybench.ROOT)
    root = str(tmp_path)
    with open(os.path.join(tinybench.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-alias", "source": "https://example.org/tiny-alias",
        "file": "bench/configs/tiny-alias.json", "reduced": [], "why": "a second binding",
    })
    bench["workloads"].append({
        "name": "tiny-alias.chat", "config": "tiny-alias", "traffic": "chat-r0.9",
        "chips": 1, "why": "the chat mix on the second binding",
    })
    for d in ("bench/configs", "bench/traffic", "bench/arch"):
        os.makedirs(os.path.join(root, d))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    with open(os.path.join(root, "bench/configs/tiny-alias.json"), "w") as f:
        json.dump(tinybench.config(name="tiny-alias", model_type="qwen3_alias"), f)
    shutil.copy(os.path.join(tinybench.ROOT, "bench/traffic/chat-r0.9.json"),
                os.path.join(root, "bench/traffic"))
    with open(os.path.join(root, "bench/arch/qwen3_alias.py"), "w") as f:
        f.write("from bench.arch.qwen3 import *  # noqa: F401,F403\n")

    cell = spec.load_cell("tiny-alias.chat", root=root)
    assert cell.config["model_type"] == "qwen3_alias"
    assert arch.names(root=root) == ["qwen3_alias"]
    alias = arch.for_config(cell.config, root=root)
    assert alias.__file__ == os.path.join(root, "bench/arch/qwen3_alias.py")
    assert arch.for_config(cell.config, root=root) is alias  # loaded once
    qwen3 = arch.binding("qwen3")
    assert alias.decode_token_flops(cell.config, 512) == qwen3.decode_token_flops(tinybench.config(), 512)
    assert alias.model_config(cell.config).n_layers == tinybench.CONFIG["num_hidden_layers"]
    with pytest.raises(FileNotFoundError, match="bench/arch/qwen3"):
        arch.for_config(tinybench.config(), root=root)  # only its own root's bindings
    assert _tree(tinybench.ROOT) == before
