import pytest

from harness import trace_reduce as tr


def ev(name, start_us, dur_us):
    return {"name": name, "start_ns": start_us * 1e3, "dur_ns": dur_us * 1e3,
            "stats": {}}


KERNEL = "%closed_call.10 = bf16[16,1,32,128]{2,1,0} custom-call(...), custom_call_target=\"tpu_custom_call\""
NOT_A_KERNEL = "%custom-call.14 = bf16[8]{0} custom-call(...), custom_call_target=\"ConcatBitcast\""


def planes():
    ops0 = [ev("%fusion.1 = bf16[16,128,4096]{2,1,0} fusion(...)", 0, 100),
            ev("%fusion.1 = bf16[16,128,4096]{2,1,0} fusion(...)", 300, 100),
            ev(KERNEL, 50, 100),            # overlaps the first fusion
            ev(KERNEL, 900, 100),
            ev(NOT_A_KERNEL, 310, 10)]      # nested in the second fusion
    mods0 = [ev("jit_step(1)", 0, 400), ev("jit__where(2)", 900, 100)]
    ops1 = [ev("%copy.1 = bf16[8]{0} copy(...)", 0, 500)]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops0},
            {"name": "XLA Modules", "events": mods0}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": ops1}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [ev("whatever", 0, 5000)]}]},
    ]


def test_busy_is_the_union_averaged_over_devices():
    r = tr.reduce_planes(planes())
    # device 0: [0,150) + [300,400) + [900,1000) = 350 us; device 1: 500
    assert r["busy_s"] == pytest.approx((350e-6 + 500e-6) / 2)
    assert r["window_s"] == pytest.approx(1000e-6)
    assert r["devices"] == 2
    idle_share = 1 - r["busy_s"] / r["window_s"]
    assert idle_share == pytest.approx(1 - 0.425)


def test_per_op_sums_and_kernels():
    r = tr.reduce_planes(planes())
    ops = dict(r["device_ops"])
    fusion = [k for k in ops if k.startswith("fusion.1")]
    # self time: the second fusion holds a 10 us op of its own line
    assert fusion and ops[fusion[0]] == pytest.approx(190e-6)
    kernels = [k for k in r["kernels"] if k["device"] == 0]
    assert len(kernels) == 2
    assert sum(k["dur_s"] for k in kernels) == pytest.approx(200e-6)
    assert all(len(name) <= 96 and " " not in name for name in ops)


def test_longest_gaps_are_labelled_by_the_programs_around_them():
    r = tr.reduce_planes(planes())
    assert r["idle_gaps"][0] == ["after_jit_step_before_jit__where",
                                 pytest.approx(500e-6)]
    assert r["idle_gaps"][1][1] == pytest.approx(150e-6)


def test_union_and_gaps():
    assert tr.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert tr.gaps_of([(0, 10), (5, 20), (30, 40)]) == [(20, 30)]
    assert tr.union_ns([]) == 0


def test_no_device_plane_is_no_busy_time():
    r = tr.reduce_planes([p for p in planes() if "host" in p["name"]])
    assert r["busy_s"] == 0.0 and r["kernels"] == []
