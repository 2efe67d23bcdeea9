"""Host spans against the device trace (``spans.py``), the round's phases
from its scopes (``scopes.py``), and the readers on recorded TPU v5e
traces."""
import math
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import scopes  # noqa: E402
import spans  # noqa: E402
import spec  # noqa: E402
import trace_reduce as TR  # noqa: E402

RECORDED = BENCH / "data" / "trace_phi3_hermes_2pod.json.gz"
RECORDED_SPANS = BENCH / "data" / "trace_phi3_hermes_2pod_spans.json.gz"
SKEW = 1000.0     # the hand-built device clock runs this far behind


def _dev(line, name, start, end, op=None):
    e = {"dev": 0, "line": line, "name": name, "start": start - SKEW,
         "dur": float(end - start)}
    if line != "modules":
        e["op"] = op or name.rsplit(".", 1)[0]
    return e


def _host(name, start, end, step=None):
    e = {"dev": -1, "line": "host", "name": name, "start": float(start),
         "dur": float(end - start)}
    if step is not None:
        e["step"] = step
    return e


def _events():
    """Two pod steps and a round on the device, in host time less SKEW;
    the host dispatches each just ahead of it and fetches after the
    round."""
    return [
        _dev("modules", "jit_pod_step", 200, 400),
        _dev("ops", "fusion.1", 200, 300),
        _dev("ops", "fusion.2", 320, 400),
        _dev("modules", "jit__lambda_", 400, 600),
        _dev("ops", "fusion.7", 400, 600),
        _dev("modules", "jit_pod_step", 900, 1100),
        _dev("ops", "fusion.1", 900, 1100),
        _host("train/batch", 0, 100, 0),
        _host("train/step", 100, 150, 0),
        _host("train/eval", 150, 170, 0),
        _host("train/round", 170, 260, 0),
        _host("train/eval", 240, 258, 0),     # eval_if_push, in the round
        _host("train/fetch", 260, 650, 0),
        _host("train/log", 650, 700, 0),
        _host("train/batch", 700, 850, 1),
        _host("train/step", 850, 880, 1),
    ]


def test_clock_skew_is_the_worst_lead_and_fetches_bound_it():
    a = spans.attribute(_events(), [0])
    # step 1 starts 50 ns after its span in host time: the device clock
    # reads it 950 ns ahead of the span; the fetch ends 100 ns after the
    # round, so the shift could grow by 100 ns at most
    assert a["clock_skew_us"] == pytest.approx(0.950)
    assert a["clock_slack_us"] == pytest.approx(0.100)


def test_idle_goes_to_the_innermost_span_and_sums_exactly():
    ev = _events()
    a = spans.attribute(ev, [0])
    s = TR.summarize(ev, [0])
    idle = s["window_s"] - s["busy_s"]
    assert sum(a["idle_s"].values()) == pytest.approx(idle, abs=1e-15)
    assert a["idle_s"] == pytest.approx({
        # the gap inside the first step, [300, 320] less the 50 ns shift
        "train/eval": 8e-9, "train/round": 2e-9,
        # and the gap after the round, [600, 900] shifted likewise
        "train/fetch": 110e-9, "train/log": 50e-9, "train/batch": 150e-9})
    assert a["idle_ms_per_step"]["train/batch"] == pytest.approx(75e-6)


def test_moving_the_device_by_the_slack_bounds_the_split():
    a = spans.attribute(_events(), [0], extra_shift=100.0)
    assert a["idle_s"] == pytest.approx({
        "train/fetch": 20e-9, "train/log": 50e-9, "train/batch": 150e-9,
        "train/step": 30e-9, "-": 70e-9})
    assert a["clock_slack_us"] == pytest.approx(0.0)


def test_gap_names_lead_with_the_span():
    a = spans.attribute(_events(), [0])
    names = [g[0] for g in a["idle_gaps"]]
    assert names[0] == "train/batch | after jit__lambda_ before jit_pod_step"
    assert names[1] == "train/fetch | after jit_pod_step before jit__lambda_"
    assert all(spans.SPAN.match(n.split(" | ")[0]) for n in names)


def test_without_spans_everything_is_untraced():
    ev = [e for e in _events() if e["line"] != "host"]
    a = spans.attribute(ev, [0])
    assert a["clock_skew_us"] is None
    assert set(a["idle_s"]) == {spans.NONE}
    assert a["idle_gaps"][0][0].startswith("- | after")


HLO = """\
HloModule jit__lambda, is_scheduled=true

%fused_computation.3 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %mul.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(<lambda>)/cond/branch_1_fun/hermes/encode/mul"}
  ROOT %add.1 = f32[4]{0} add(%mul.1, %param_0), metadata={op_name="jit(<lambda>)/cond/branch_1_fun/hermes/encode/add"}
}

ENTRY %main.9 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.3
  %fusion.4 = f32[4]{0} fusion(%fusion.3), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(<lambda>)/hermes/merge/hermes/refresh/select_n"}
  %while.2 = f32[4]{0} while(%fusion.4), condition=%c, body=%b, metadata={op_name="jit(<lambda>)/hermes/merge/while"}
  %copy.1 = f32[4]{0} copy(%while.2)
  ROOT %reduce.5 = f32[4]{0} reduce(%copy.1), metadata={op_name="jit(<lambda>)/hermes/gatekeeper/x"}
}
"""


def test_op_phases_from_compiled_text():
    m = scopes.op_phases(HLO)
    assert m["fusion.3"] == "encode"      # from its fused instructions
    assert m["fusion.4"] == "refresh"     # the innermost scope
    assert "while.2" not in m             # holds others: no phase
    assert "copy.1" not in m and "reduce.5" not in m


def test_phase_ms_per_round_execution():
    summary = {"per_chip": [
        {"executions": {"jit__lambda_": [{"fusion.3": 0.002, "copy.1": 1.0},
                                         {"fusion.3": 0.004}],
                        "jit_pod_step": [{"fusion.3": 9.0}]}},
        {"executions": {"jit__lambda_": [{"fusion.3": 0.006}]}}]}
    phases = {"fusion.3": "encode"}
    # chip 0: 6 ms over 2 rounds; chip 1: 6 ms over 1
    assert scopes.phase_ms(summary, phases, "encode") == pytest.approx(4.5)
    assert scopes.phase_ms(summary, phases, "merge") is None
    assert scopes.phase_ms(summary, {}, "encode") is None


def test_a_program_without_scopes_reads_none():
    cell = {"traffic": {"loop": "single"}}
    ctx = {"cell": cell, "trace": {"per_chip": []}}
    for m in ("round_gate_ms", "round_encode_ms", "round_merge_ms",
              "round_refresh_ms"):
        assert spec.metric_reader(m, BENCH.parent)(ctx) is None


# The values each reader gave on the first recorded trace before the
# program carried spans or scopes (the readers' files are unchanged).
BEFORE = {"gate_eval_ms": 28.645908499999997, "hbm_peak_gb": 10.021234688,
          "idle_share": 0.013263649583528103, "mfu": 12.06700507614213,
          "pod_step_ms": 104.21906966666668, "round_ms": 248.3977375}


@pytest.mark.parametrize("metric", sorted(BEFORE))
def test_existing_readers_read_the_recorded_trace_as_before(metric):
    ctx = {"trace": TR.summarize(TR.load_events(str(RECORDED)), [0]),
           "memory_peak_bytes": 10021234688, "flops_per_token": 2.0e9,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "tokens_per_s": 11886.0}
    assert spec.metric_reader(metric, BENCH.parent)(ctx) == BEFORE[metric]


def test_round_phases_from_a_fresh_compile():
    """The reader's own compile of a small cell's round maps the round's
    instructions to every phase an unplaced round runs."""
    import harness
    from repro.launch.train import _preset
    traffic = {"loop": "hermes", "pods": 2,
               "hermes": {"lam": 1, "alpha": -0.5, "compression": "int4",
                          "error_feedback": True, "kernel_dispatch": "off"}}
    ctx = {"cell": {"traffic": traffic, "model": _preset("lmtiny")}}
    phases = scopes.round_op_phases(ctx)
    assert set(phases.values()) == {"gate", "encode", "merge", "refresh"}
    assert scopes.round_op_phases(ctx) is phases       # compiled once
    assert harness.hermes_config(traffic).compression == "int4"


def test_trim_keeps_whole_steps_and_tags_round_phases():
    import record_trace
    ev = _events()
    kept = record_trace.trim(ev, {"fusion.7": "merge", "fusion.1": "gate"},
                             keep=1, log_every=1)
    # every step logs; the first one's pod step ends where the round starts
    assert {e.get("step") for e in kept if e["line"] == "host"} == {0}
    assert [e["name"] for e in kept if e["line"] == "modules"] == [
        "jit_pod_step"]
    # a pod step's op is not the round's, whatever its name
    assert not any("phase" in e for e in kept)
    kept = record_trace.trim(ev, {"fusion.7": "merge"}, keep=2, log_every=2)
    assert [e.get("phase") for e in kept if e["name"] == "fusion.7"] == [
        "merge"]


def test_recorded_spans_trace_reads_every_new_number():
    """Three Hermes iterations of phi3-mini-3.8b around a log fetch, traced
    on one TPU v5e with the program's spans, each round op tagged with its
    scope's phase by ``record_trace.py``."""
    ev = TR.load_events(str(RECORDED_SPANS))
    s = TR.summarize(ev, [0])
    ctx = {"trace": s, "round_op_phases": {e["name"]: e["phase"]
                                           for e in ev if "phase" in e}}
    phases = {m: spec.metric_reader(m, BENCH.parent)(ctx)
              for m in ("round_gate_ms", "round_encode_ms", "round_merge_ms",
                        "round_refresh_ms")}
    assert all(v is not None and math.isfinite(v) and v > 0
               for v in phases.values()), phases
    n, t = TR.match(s["modules"], scopes.ROUND)
    # the rest of the round is copies the compiler adds, of no scope
    assert 0.5 < sum(phases.values()) / (t / n * 1e3) < 0.7
    a = spans.attribute(ev, [0])
    assert sum(a["idle_s"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-9)
    per_step = a["idle_ms_per_step"]
    for label in ("train/fetch", "train/batch", spans.NONE):
        assert math.isfinite(per_step.get(label, 0.0))
    assert per_step["train/batch"] > 0
    assert math.isfinite(a["clock_skew_us"]) and a["clock_slack_us"] >= 0
    assert all(spans.SPAN.match(g[0].split(" | ")[0]) or
               g[0].startswith(spans.NONE) for g in a["idle_gaps"])
