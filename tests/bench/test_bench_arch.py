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
from bench.lib.trace import Reduced

MS = 1_000_000  # ns


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
        assert all(callable(getattr(b, p)) for p in arch.PROVIDES if p not in ("TINY", "KERNELS"))
        assert isinstance(b.KERNELS, tuple) and b.KERNELS
        assert all(callable(getattr(b, f"{k}_cost")) for k in b.KERNELS)
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


def _stub(root, model_type, body):
    """A binding under `root` that re-exports Qwen3 and adds `body`."""
    os.makedirs(os.path.join(root, "bench/arch"), exist_ok=True)
    with open(os.path.join(root, f"bench/arch/{model_type}.py"), "w") as f:
        f.write("from bench.arch.qwen3 import *  # noqa: F401,F403\n\n" + body)
    return arch.binding(model_type, root=str(root))


def test_the_trace_reader_attributes_the_bindings_own_kernels(tmp_path):
    """A binding that names a kernel of its own has that kernel's events
    summed per program, and a kernel it does not name is an ordinary op."""
    b = _stub(tmp_path, "mla_stub", (
        'KERNELS = ("mla_decode",)\n\n\n'
        "def mla_decode_cost(config, ctxs):\n"
        '    return {"flops": 1.0, "bytes": 1.0}\n'
    ))
    assert b.KERNELS == ("mla_decode",)
    ops = [
        ("mla_decode", 1 * MS, 2 * MS),
        ("mla_decode.4", 2 * MS, 4 * MS),
        ("decode_attention", 4 * MS, 5 * MS),
        ("fusion.2", 6 * MS, 7 * MS),
    ]
    modules = [("jit_decode", 0, 5 * MS), ("jit_prefill", 6 * MS, 8 * MS)]
    red = Reduced(window=(0, 10 * MS), ops=ops, modules=modules, host=[], kernels=b.KERNELS)
    assert [p.kind for p in red.programs] == ["decode", "prefill"]
    assert red.programs[0].kernels == {"mla_decode": 3 * MS}
    assert red.programs[1].kernels == {}
    device_ops = dict(map(tuple, red.breakdown()["device_ops"]))
    assert device_ops["decode:mla_decode"] == pytest.approx(3e-3)
    assert device_ops["decode:decode_attention"] == pytest.approx(1e-3)


def test_a_binding_that_names_a_kernel_without_its_cost_is_refused(tmp_path):
    with pytest.raises(AttributeError, match=r"\['mla_decode_cost'\]"):
        _stub(tmp_path, "no_cost", 'KERNELS = ("decode_attention", "mla_decode")\n')
    with pytest.raises(AttributeError, match="mla_decode_cost"):  # not kept half-loaded
        arch.binding("no_cost", root=str(tmp_path))
