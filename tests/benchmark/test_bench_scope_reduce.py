"""Device time by named scope: the map from a compiled program's text,
the attribution of trace events to programs and scopes on hand-made
events, and both on the trace recorded from the chip."""
import glob
import os

import pytest

from bench_util import ROOT

from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr

FIXTURES = os.path.join(ROOT, "tests", "benchmark", "fixtures")
SCOPES = ("moe.experts", "moe.route", "kda")

HLO = """
HloModule jit_decode_step_ling, entry_computation_layout={()->f32[]}

%fused_computation.7 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %multiply.3 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(decode_step_ling)/layer1/kda/mul"}
}

ENTRY %main {
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(decode_step_ling)/layer1/kda/mul" source_file="x.py"}
  %ragged-dot-none.16 = bf16[1024,768]{1,0} custom-call(%x, %w), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %sort.2 = s32[1024]{0} sort(%ids), metadata={op_name="jit(decode_step_ling)/layer1/moe.experts/sort"}
  %top.1 = f32[8,8]{1,0} custom-call(%s), metadata={op_name="jit(decode_step_ling)/layer1/moe.route/top_k"}
  %copy.5 = f32[8]{0} copy(%b)
  ROOT %add.9 = f32[8]{0} add(%c, %d), metadata={op_name="jit(decode_step_ling)/layer1/add"}
}
"""


def test_scope_map_reads_op_names_and_falls_back_to_instruction_names():
    got = sr.scope_map(HLO, SCOPES, by_name=(("ragged-dot", "moe.experts"),))
    assert got["fusion.7"] == "kda" and got["multiply.3"] == "kda"
    assert got["ragged-dot-none.16"] == "moe.experts"
    assert got["sort.2"] == "moe.experts" and got["top.1"] == "moe.route"
    assert got["copy.5"] == "other" and got["add.9"] == "other"
    # without the rule the compiler's kernel belongs to no scope
    assert sr.scope_map(HLO, SCOPES)["ragged-dot-none.16"] == "other"


def test_a_scope_is_a_whole_component_of_the_path():
    text = ('  %a.1 = f32[] add(%x, %y), metadata={op_name="jit(f)/kda_like/'
            'add"}\n  %b.2 = f32[] add(%x, %y), metadata={op_name="jit(f)/'
            'layer0/kda/add"}\n')
    assert sr.scope_map(text, ("kda",)) == {"a.1": "other", "b.2": "kda"}


def test_reduce_on_hand_made_events():
    maps = {"jit_step": {"fusion.1": "kda", "while.2": "kda",
                         "fusion.3": "moe.experts"},
            "jit_prefill": {"fusion.1": "moe.experts"}}
    modules = [("jit_step(11)", 0.0, 4.0), ("jit_prefill(12)", 5.0, 8.0),
               ("jit_other(13)", 8.5, 9.0), ("jit_step(11)", 9.5, 12.0)]
    ops = [("%fusion.1 = f32[8]{0} fusion(%a)", 0.0, 1.0),
           ("%while.2 = (f32[8]) while(%t)", 1.0, 3.0),
           ("%fusion.3 = f32[8]{0} fusion(%b)", 1.5, 2.5),   # in the while
           ("%mystery.9 = f32[8]{0} fusion(%b)", 3.0, 3.5),
           ("%fusion.1 = f32[8]{0} fusion(%a)", 5.0, 7.0),   # the prefill's
           ("%fusion.1 = f32[8]{0} fusion(%a)", 8.5, 9.0),   # no map: left out
           ("%fusion.1 = f32[8]{0} fusion(%a)", 9.5, 11.5)]  # clipped at 10
    got = sr.reduce(ops, modules, maps, (0.0, 10.0))
    assert set(got) == {"jit_step", "jit_prefill"}
    assert got["jit_step"]["kda"] == pytest.approx(1.0 + 1.0 + 0.5)
    assert got["jit_step"]["moe.experts"] == pytest.approx(1.0)
    assert got["jit_step"]["unmatched"] == pytest.approx(0.5)
    assert got["jit_prefill"] == {"moe.experts": pytest.approx(2.0)}
    total = sr.totals(got)
    assert total["moe.experts"] == pytest.approx(3.0)
    assert total["kda"] == pytest.approx(2.5)


def test_an_event_outside_every_program_is_left_out():
    got = sr.reduce([("%fusion.1 = f32[] fusion()", 5.0, 6.0)],
                    [("jit_step(1)", 0.0, 4.0)], {"jit_step": {}}, (0, 10))
    assert got == {}


def test_recorded_trace_from_the_chip():
    """The recorded ``lm_train`` trace: every operation lies in a
    ``jit_train_step`` program, and with the flash kernels' instruction
    names mapped to a scope, that scope's device time is the kernels'
    time as ``trace_reduce`` adds it up."""
    paths = glob.glob(os.path.join(FIXTURES, "*.xplane.pb"))
    assert paths
    ops, modules, window = sr.load(paths[0])
    assert window is not None and ops and modules
    assert {sr.program_of(n) for n, _s, _e in modules} == {"jit_train_step"}
    names = {sr._EVENT.match(n).group(1) for n, _s, _e in ops
             if sr._EVENT.match(n)}
    mapping = {}
    for full, _s, _e in ops:
        m = sr._EVENT.match(full)
        if m:
            mapping[m.group(1)] = "flash" if tr.MOSAIC_CALL in full \
                else "other"
    assert len(mapping) == len(names)
    got = sr.reduce(ops, modules, {"jit_train_step": mapping}, window)
    devices, host = tr.load(paths[0], 1)
    whole = tr.reduce(devices, host, "host")
    assert got["jit_train_step"]["flash"] == pytest.approx(
        whole["custom_call_s"], rel=1e-6)
    assert "unmatched" not in got["jit_train_step"]
    assert sum(got["jit_train_step"].values()) <= whole["busy_s"] * 1.0001


def test_reduce_dir_without_a_trace_gives_nothing(tmp_path):
    assert sr.reduce_dir(str(tmp_path), {}) == {}
