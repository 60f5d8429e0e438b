"""Device time per layer from the program's named scopes
(`harness.scopes` and its readers), on a synthetic compiled step and a
synthetic trace whose answers are known."""
import types

import pytest

from harness import hlo, metrics, scopes, trace as tr

MS = 1e-3
STEP = "jit(train_step)"

# a step: attention forward and backward, an FFN recompute inside the
# loop body, the codec kernel, AdamW, a layout copy with no metadata
HLO = f"""HloModule jit_train_step

%fc.1 (p.1: f32[8,8], q.1: f32[8,8]) -> f32[8,8] {{
  %p.1 = f32[8,8] parameter(0)
  %q.1 = f32[8,8] parameter(1)
  ROOT %dot.1 = f32[8,8] dot(%p.1, %q.1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{STEP}/jvp(attn)/dot_general"}}
}}

%body.2 (b.2: f32[8,8]) -> f32[8,8] {{
  %b.2 = f32[8,8] parameter(0)
  ROOT %fusion.2 = f32[8,8] fusion(%b.2, %b.2), kind=kOutput, calls=%fc.1, metadata={{op_name="{STEP}/transpose(jvp())/while/body/checkpoint/rematted_computation/ffn/dot_general"}}
}}

%cond.2 (c.2: f32[8,8]) -> pred[] {{
  %c.2 = f32[8,8] parameter(0)
  ROOT %lt.2 = pred[] constant(false)
}}

ENTRY %main.9 (a.9: f32[8,8]) -> f32[8,8] {{
  %a.9 = f32[8,8] parameter(0)
  %fusion.1 = f32[8,8] fusion(%a.9, %a.9), kind=kOutput, calls=%fc.1, metadata={{op_name="{STEP}/jvp(attn)/dot_general"}}
  %delta_quantize_pack.3 = f32[8,8] custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(boundary)/jit(delta_quantize_pack)/delta_quantize_pack/pallas_call"}}
  %while.4 = f32[8,8] while(%delta_quantize_pack.3), condition=%cond.2, body=%body.2, backend_config={{"known_trip_count":{{"n":"1"}}}}
  %fusion.5 = f32[8,8] fusion(%while.4, %while.4), kind=kOutput, calls=%fc.1, metadata={{op_name="{STEP}/transpose(jvp(attn))/dot_general"}}
  %copy.6 = f32[8,8] copy(%fusion.5)
  ROOT %fusion.7 = f32[8,8] fusion(%copy.6), kind=kLoop, calls=%fc.1, metadata={{op_name="{STEP}/adamw/sub"}}
}}
"""

# two steps of 10 ms, each op's time (ms) as below; the while loop's
# event spans its body's op and is left out
PER_STEP = [("fusion.1", 2.0), ("delta_quantize_pack.3", 0.5),
            ("while.4", 1.5), ("fusion.2", 1.5), ("fusion.5", 3.0),
            ("copy.6", 1.0), ("fusion.7", 1.5)]


def _events(t0):
    out, t = [], t0
    for n, ms in PER_STEP:
        if n == "while.4":
            out.append((n, t, t + ms * MS))   # spans fusion.2
            continue
        out.append((n, t, t + ms * MS))
        t += ms * MS
    return out


def _run(text=HLO):
    ops = _events(1 * MS) + _events(13 * MS) + [("fusion.1", 40 * MS,
                                                 41 * MS)]
    trace = tr.Trace(ops={0: ops},
                     modules={0: [("jit_train_step(7)", 1 * MS, 11 * MS),
                                  ("jit_train_step(7)", 13 * MS, 23 * MS),
                                  ("jit_other(2)", 40 * MS, 41 * MS)]},
                     spans=[("bench.window", 0.0, 50 * MS)])
    calls = []

    def step_hlo():
        calls.append(1)
        return text
    run = types.SimpleNamespace(trace=trace, ops=hlo.op_table(text),
                                steps=2, step_hlo=step_hlo, log=lambda *a:
                                None)
    return run, calls


def _read(name, run):
    return metrics._load(name).read(run)


def test_wrappers_are_unwrapped():
    assert scopes.unwrap("transpose(jvp(attn))") == "attn"
    assert scopes.unwrap("jvp(boundary)") == "boundary"
    assert scopes.unwrap("jvp()") == ""
    assert scopes.unwrap("rematted_computation") == "rematted_computation"


@pytest.mark.parametrize("path, want", [
    (f"{STEP}/jvp(attn)/dot_general", ("attn", "forward")),
    (f"{STEP}/transpose(jvp(attn))/dot_general", ("attn", "backward")),
    (f"{STEP}/transpose(jvp())/while/body/closed_call/checkpoint/"
     f"rematted_computation/ffn/dot_general", ("ffn", "recompute")),
    # the innermost scope wins
    (f"{STEP}/jvp(ffn)/lm_head/dot_general", ("lm_head", "forward")),
    (f"{STEP}/transpose(jvp(boundary))/jit(quantize_pack)/quantize_pack/"
     f"pallas_call", ("boundary", "backward")),
    (f"{STEP}/jvp()/slice", (None, "forward")),
    ("state['params']['embed']", (None, "forward")),
])
def test_innermost_scope_and_phase(path, want):
    assert scopes.classify(path) == want


def test_table_names_every_instruction_and_is_built_once():
    run, calls = _run()
    tab = scopes.table(HLO)
    assert tab["fusion.1"] == ("attn", "forward")
    assert tab["fusion.2"] == ("ffn", "recompute")
    assert tab["fusion.5"] == ("attn", "backward")
    assert tab["copy.6"] == (None, "forward")
    for name in ("attn_ms", "ffn_ms", "adamw_ms", "unscoped_ms"):
        _read(name, run)
    assert len(calls) == 1


def test_self_times_per_step():
    run, _ = _run()
    assert _read("attn_ms", run) == pytest.approx(5.0)     # 2 + 3
    assert _read("ffn_ms", run) == pytest.approx(1.5)
    assert _read("recompute_ms", run) == pytest.approx(1.5)
    assert _read("boundary_ms", run) == pytest.approx(0.5)
    assert _read("adamw_ms", run) == pytest.approx(1.5)
    assert _read("unscoped_ms", run) == pytest.approx(1.0)  # copy.6
    # scopes the step does not run read 0 ms, not nothing
    assert _read("store_ms", run) == 0.0
    assert _read("vocab_ms", run) == 0.0


def test_control_ops_are_left_out_and_the_scopes_partition_the_step():
    run, _ = _run()
    assert run.ops["while.4"]["kind"] == "control"
    names = ("attn_ms", "ffn_ms", "vocab_ms", "adamw_ms", "boundary_ms",
             "store_ms", "unscoped_ms")
    total = sum(_read(n, run) for n in names)
    per_step = sum(ms for n, ms in PER_STEP if n != "while.4")
    assert total == pytest.approx(per_step)                 # 9.5 ms
    # the ops outside the step programs (jit_other) count nowhere
    assert [o for o in scopes.step_ops(run) if o[0] == "fusion.1"] == [
        ("fusion.1", pytest.approx(2 * MS))] * 2


def test_unscoped_operations_are_listed_for_the_log():
    run, _ = _run()
    assert scopes.unscoped(run) == [["copy.6", pytest.approx(1.0)]]


def test_a_program_without_scopes_reads_nothing():
    bare = HLO
    for p in ("jvp(attn)", "transpose(jvp(attn))", "/ffn/", "jvp(boundary)",
              "/adamw/"):
        bare = bare.replace(p, "jvp()" if "(" in p else "/")
    run, _ = _run(bare)
    assert scopes.table(bare) == {}
    for name in ("attn_ms", "recompute_ms", "unscoped_ms"):
        assert _read(name, run) is None


def test_no_device_trace_reads_nothing():
    run, calls = _run()
    run.trace = tr.Trace(spans=[("bench.window", 0.0, 1.0)])
    assert _read("attn_ms", run) is None
    assert calls == []          # the step's HLO is not even asked for


def test_the_scope_names_are_the_programs():
    from repro import tracing
    assert scopes.SCOPES == tracing.SCOPES
