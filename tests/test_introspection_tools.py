"""contrib.memory_usage_calc / contrib.op_frequence / debugger —
program-introspection parity surface.

Reference analogs: contrib/memory_usage_calc.py:46, contrib/
op_frequence.py:23, fluid/debugger.py.
"""

import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers

ROOT = os.path.join(os.path.dirname(__file__), os.pardir)


def _small_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4])
        h = layers.fc(x, size=8, act="relu")
        h2 = layers.fc(h, size=8, act="relu")
        loss = layers.mean(h2)
    return main, startup, loss


def test_memory_usage_estimate():
    from paddle_tpu.contrib.memory_usage_calc import memory_usage

    main, _, _ = _small_program()
    val, unit = memory_usage(main, batch_size=32)
    assert unit in ("B", "KB", "MB", "GB")
    assert val > 0
    # scales with batch (activations have a -1 batch dim)
    v2, u2 = memory_usage(main, batch_size=64)
    as_bytes = {"B": 1, "KB": 1 << 10, "MB": 1 << 20, "GB": 1 << 30}
    assert v2 * as_bytes[u2] > val * as_bytes[unit]
    with pytest.raises(ValueError):
        memory_usage(main, batch_size=0)


def test_contrib_namespace_reexports():
    # ported user code calls these off fluid.contrib directly
    from paddle_tpu import contrib

    assert callable(contrib.memory_usage)
    assert callable(contrib.op_freq_statistic)
    assert contrib.memory_usage_calc.memory_usage is contrib.memory_usage


def test_compiled_memory_usage():
    from paddle_tpu.contrib.memory_usage_calc import compiled_memory_usage

    main, startup, loss = _small_program()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    feed = {"x": np.zeros((16, 4), "float32")}
    got = compiled_memory_usage(exe, main, feed, fetch_list=[loss])
    if got is not None:  # backend-dependent; CPU jaxlib reports it
        # peak bytes must at least cover the two fc weight matrices
        assert got >= (4 * 8 + 8 * 8) * 4


def test_op_freq_statistic():
    from paddle_tpu.contrib.op_frequence import op_freq_statistic

    main, _, _ = _small_program()
    uni, adj = op_freq_statistic(main)
    assert uni["mul"] == 2  # two fc layers
    assert uni["relu"] == 2
    assert any("->" in k for k in adj)
    # sorted most-frequent first
    counts = list(uni.values())
    assert counts == sorted(counts, reverse=True)


def test_debugger_pprint_and_dot(tmp_path):
    from paddle_tpu import debugger

    main, startup, loss = _small_program()
    fluid.optimizer.SGD(learning_rate=0.1).minimize(
        loss, startup_program=startup)
    text = debugger.pprint_program_codes(main, file=open(os.devnull, "w"))
    assert "mul(" in text and "block_0 {" in text
    assert "sgd(" not in text  # optimize hidden by default
    assert "@GRAD" not in text  # grad vars hidden with the backward ops
    text_bwd = debugger.pprint_block_codes(
        main.global_block(), show_backward=True, file=open(os.devnull, "w"))
    assert "sgd(" in text_bwd

    dot_path = str(tmp_path / "g.dot")
    dot = debugger.draw_block_graphviz(main.global_block(),
                                       highlights=[loss.name], path=dot_path)
    assert os.path.exists(dot_path)
    assert "digraph" in dot and 'fillcolor="yellow"' in dot
    assert dot.count('shape="ellipse"') == len(main.global_block().ops)


def test_graphviz_and_net_drawer(tmp_path):
    from paddle_tpu import net_drawer
    from paddle_tpu.graphviz import Graph

    g = Graph(title="t", rankdir="TB")
    a = g.node("in put", prefix="var")   # label with a space quotes fine
    b = g.node("op", shape="oval")
    g.edge(a, b, label="x")
    code = g.code()
    assert code.startswith('digraph "t" {') and '"in put"' in code
    assert "->" in code
    # backslash-safe quoting: a trailing backslash must not eat the quote
    from paddle_tpu.graphviz import crepr

    assert crepr("a\\") == '"a\\\\"'

    main, startup, _loss = _small_program()
    out = tmp_path / "net.dot"
    drawn = net_drawer.draw_graph(startup, main, path=str(out))
    assert out.exists()
    text = out.read_text()
    # every main-block op drawn, params styled as filled boxes
    n_ops = len(startup.global_block().ops) + len(main.global_block().ops)
    assert sum(1 for n in drawn.nodes if n.name.startswith("op_")) >= n_ops
    assert "#FFF3CF" in text  # at least one Parameter node
