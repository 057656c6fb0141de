"""The trace reduction on a small synthetic trace whose answers are worked
out by hand (times in ns)."""
import pytest

from bench import trace_reduce

HOST = [("bench.window", 0, 1000), ("bench.step_dispatch", 10, 100),
        ("bench.batch_make", 100, 300), ("bench.step_wait", 300, 900),
        ("other.span", 0, 1000)]
DEV0 = {"ops": [("fusion.1", 50, 250), ("all-gather.2", 200, 400),
                ("custom-call.3", 450, 600), ("all-reduce.4", 650, 700),
                ("fusion.9", 1100, 1200)],  # after the window: left out
        "modules": [("jit_step(1)", 50, 700)]}
DEV1 = {"ops": [("fusion.1", 0, 1000)], "modules": []}


def test_merge_and_subtract():
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace_reduce.merge([(0, 10), (2, 3)]) == [(0, 10)]


def test_one_device():
    red = trace_reduce.reduce({"host": HOST, "devices": [DEV0]}, n_devices=1)
    assert red["window_s"] == pytest.approx(1000e-9)
    # busy: [50, 400] + [450, 600] + [650, 700]
    assert red["busy_s"] == pytest.approx(550e-9)
    d = red["devices"][0]
    assert d["busy_ns"] == 550
    assert d["modules"] == [("jit_step(1)", 50, 700)]
    assert red["op_s"] == pytest.approx({"fusion.1": 200e-9, "all-gather.2": 200e-9,
                                         "custom-call.3": 150e-9,
                                         "all-reduce.4": 50e-9})
    # idle gaps by the innermost benchmark span over their middle:
    # [0, 50] in step_dispatch; [400, 450], [600, 650], [700, 1000] in step_wait
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({"step_dispatch": 50e-9, "step_wait": 400e-9})
    assert red["breakdown"]["device_ops"][0][1] == pytest.approx(200e-9)
    assert [n for n, _, _ in red["host"]] == ["window", "step_dispatch",
                                              "batch_make", "step_wait"]


def test_two_devices_average():
    red = trace_reduce.reduce({"host": HOST, "devices": [DEV0, DEV1]}, n_devices=2)
    assert red["busy_s"] == pytest.approx((550e-9 + 1000e-9) / 2)
    assert red["op_s"]["fusion.1"] == pytest.approx((200e-9 + 1000e-9) / 2)


def test_no_device_ops_is_an_error():
    with pytest.raises(RuntimeError):
        trace_reduce.reduce({"host": HOST, "devices": [{"ops": [], "modules": []}]}, 1)


def test_window_from_device_ops_without_span():
    red = trace_reduce.reduce({"host": [], "devices": [DEV1]}, 1)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(1000e-9)


def test_op_names_and_containers():
    assert trace_reduce.op_name("%while.260 = (s32[], bf16[16,2048]) while(...)") == "while.260"
    assert trace_reduce.op_name("%jvp_jit_quantize_packed__.8 = (u8[50304,1024]) "
                                "custom-call(f32[50304,1024] %fusion.1)") == \
        "jvp_jit_quantize_packed__.8"
    dev = {"ops": [("while.1", 0, 1000), ("fusion.2", 100, 300),
                   ("all-gather.3", 250, 500)], "modules": []}
    red = trace_reduce.reduce({"host": [], "devices": [dev]}, 1)
    # the while loop only contains the others: out of the op times and of
    # the busy time
    assert "while.1" not in red["op_s"]
    assert red["op_s"] == pytest.approx({"fusion.2": 200e-9, "all-gather.3": 250e-9})
    assert red["busy_s"] == pytest.approx(400e-9)


def test_gap_inside_a_loop_is_idle():
    """A ``while`` op spans its whole body; the gap between two of its body's
    ops, while the host waits in a span, is idle and is labelled by it."""
    host = [("bench.window", 0, 1000), ("bench.step_wait", 0, 1000),
            ("bench.sched_step", 350, 650)]
    dev = {"ops": [("while.7", 0, 1000), ("fusion.1", 0, 300),
                   ("conditional.2", 700, 1000), ("fusion.3", 700, 1000)],
           "modules": [("jit_decode(1)", 0, 1000)]}
    red = trace_reduce.reduce({"host": host, "devices": [dev]}, 1)
    assert red["busy_s"] == pytest.approx(600e-9)
    assert dict(red["breakdown"]["idle_gaps"]) == pytest.approx({"sched_step": 400e-9})
    assert red["op_s"] == pytest.approx({"fusion.1": 300e-9, "fusion.3": 300e-9})
