"""Trace reduction: device-busy union, per-executable time, kernel time by
name and collective exposure, on hand-built events and on a trimmed trace
recorded on a TPU v5e."""
import gzip
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import trace_reduce as TR  # noqa: E402

RECORDED = BENCH / "data" / "trace_phi3_hermes_2pod.json.gz"


def _ev(dev, line, name, start, end, op=None):
    e = {"dev": dev, "line": line, "name": name, "start": float(start),
         "dur": float(end - start)}
    if line != "modules":
        e["op"] = op or name.rsplit(".", 1)[0]
    return e


def _events():
    out = []
    for d in (0, 1):
        out += [_ev(d, "modules", "jit_pod_step", 0, 100),
                _ev(d, "modules", "jit__lambda_", 110, 200),
                _ev(d, "modules", "jit_pod_step", 210, 300),
                _ev(d, "ops", "fusion.1", 0, 50),
                _ev(d, "ops", "convolution.2", 40, 100),
                _ev(d, "ops", "fusion.3", 110, 150),
                _ev(d, "ops", "all-gather.4", 140, 190),
                _ev(d, "ops", "pack_int4.3", 190, 200, "custom-call"),
                _ev(d, "ops", "conditional", 110, 200),
                _ev(d, "async", "copy-start.6", 0, 300),
                _ev(d, "ops", "fusion.5", 210, 300)]
    # before the first pod step: set-up, outside the window
    out.append(_ev(0, "ops", "fusion.0", -500, -400))
    return out


def test_busy_union_window_and_idle():
    s = TR.summarize(_events(), [0, 1])
    assert s["window_s"] == pytest.approx(300e-9)
    # [0,100] u [110,200] u [210,300]
    assert s["busy_s"] == pytest.approx(280e-9)
    assert s["chips"] == 2


def test_per_executable_and_kernel_time():
    s = TR.summarize(_events(), [0, 1])
    assert s["modules"]["jit_pod_step"] == pytest.approx([2, 190e-9])
    assert s["modules"]["jit__lambda_"] == pytest.approx([1, 90e-9])
    assert s["kernels"]["pack_int4.3"] == pytest.approx([1, 10e-9])
    assert TR.match(s["modules"], "pod_step") == pytest.approx((2, 190e-9))
    assert TR.kernel_in_rounds(s, "lambda", "^pack_int4") == [
        (1, pytest.approx(10e-9)), (1, pytest.approx(10e-9))]


def test_collective_exposure():
    s = TR.summarize(_events(), [0, 1])
    # all-gather [140,190]: fusion.3 covers to 150, the kernel starts at
    # 190; the conditional holding them all is no compute of its own
    assert s["exposed_s"] == pytest.approx(40e-9)
    # an asynchronous collective's span counts as collective time
    ev = _events() + [_ev(0, "async", "all-gather-start.9", 205, 215,
                          "all-gather-start")]
    s = TR.summarize(ev, [0])
    assert s["exposed_s"] == pytest.approx(45e-9)


def test_idle_gaps_are_named_by_neighbours():
    s = TR.summarize(_events(), [0, 1])
    names = [g[0] for g in s["breakdown"]["idle_gaps"]]
    assert "after jit_pod_step before jit__lambda_" in names
    top = dict(s["breakdown"]["device_ops"])
    assert "jit__lambda_/conditional" not in top
    assert top["jit_pod_step/fusion.5"] == pytest.approx(90e-9)
    assert top["jit__lambda_/all-gather.4"] == pytest.approx(50e-9)


def test_roofline_share_of_a_kernel():
    s = TR.summarize(_events(), [0, 1])
    ctx = {"trace": s, "chips": 2, "wire_bytes": {"int4_pack": 819.0},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    # 819 B per open round at 819 GB/s is 1 ns; the kernel took 10 ns
    assert TR.roofline_share(ctx, "lambda", "^pack_int4", "int4_pack",
                             per_chip_share=False) == pytest.approx(10.0)
    assert TR.roofline_share(ctx, "lambda", "^pack_int4", "int4_pack",
                             per_chip_share=True) == pytest.approx(5.0)
    assert TR.roofline_share(ctx, "lambda", "no_such", "int4_pack",
                             per_chip_share=False) is None


def test_op_name_and_code_from_hlo_text():
    assert TR.op_name("%dequant_merge_packed.7 = f32[384768,2,128]"
                      "{2,1,0:T(2,128)} custom-call(f32[3847") == (
        "dequant_merge_packed.7", "custom-call")
    assert TR.op_name("%cond.3.clone = (f32[]{:T(128)}) conditional(s32[]")\
        == ("cond.3.clone", "conditional")


def test_union_and_subtract():
    assert TR.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert TR._subtract([[0, 10]], [[2, 3], [5, 12]]) == 4


def test_load_events_reads_a_trimmed_trace(tmp_path):
    p = tmp_path / "t.json.gz"
    with gzip.open(p, "wt") as f:
        json.dump(_events(), f)
    assert TR.load_events(str(p)) == _events()


def test_recorded_trace_reduces_as_counted_by_hand():
    """Two Hermes iterations of phi3-mini-3.8b (2 pods, a round after each
    step) traced on one TPU v5e, trimmed to the executions between the
    first and the last pod step kept."""
    ev = TR.load_events(str(RECORDED))
    s = TR.summarize(ev, [0])
    mods = [e for e in ev if e["line"] == "modules"]
    steps = [e for e in mods if "pod_step" in e["name"]]
    lo = min(e["start"] for e in steps)
    hi = max(e["start"] + e["dur"] for e in steps)
    assert s["window_s"] == pytest.approx((hi - lo) * 1e-9)
    # busy: sweep the op intervals by hand
    iv = sorted((max(e["start"], lo), min(e["start"] + e["dur"], hi))
                for e in ev if e["line"] == "ops")
    busy, end = 0.0, lo
    for a, b in iv:
        if b <= end:
            continue
        busy += b - max(a, end)
        end = b
    assert s["busy_s"] == pytest.approx(busy * 1e-9)
    assert 0.99 < s["busy_s"] / s["window_s"] <= 1.0
    # per executable: three pod steps of about 104 ms, two rounds
    n, t = TR.match(s["modules"], "pod_step")
    assert n == 3 and t == pytest.approx(
        sum(e["dur"] for e in steps) * 1e-9)
    assert 0.10 < t / n < 0.11
    assert TR.match(s["modules"], "lambda")[0] == 2
    # kernel time by name: the pack kernel, once per leaf and open round
    packs = [e for e in ev if e["line"] == "ops"
             and e["name"].startswith("pack_int4")]
    n, t = TR.match(s["kernels"], r"^pack_int4")
    assert n == len(packs) == 24
    assert t == pytest.approx(sum(e["dur"] for e in packs) * 1e-9)
    assert TR.kernel_in_rounds(s, "lambda", r"^pack_int4") == [
        (2, pytest.approx(t))]
    # one chip: no collective, so nothing exposed
    assert not any(TR.COLLECTIVE.search(e.get("op", "")) for e in ev)
    assert s["exposed_s"] == 0.0
