"""``horovod_tpu.monitor.hlo_owners``: the three rules on hand-written HLO
text, and the map on the compiled text of the benchmark's three tiny steps
(docs/observability.md, "Scopes in the device trace")."""

import os
import re
import sys

import jax
import pytest

import horovod_tpu as hvd
from horovod_tpu.monitor import hlo_owners as ho
from horovod_tpu.monitor.span_audit import DEVICE_SCOPES

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark"))
import bench_tiny as tiny  # noqa: E402
import bench_tiny_afmoe as tiny_afmoe  # noqa: E402
import bench_tiny_sparse as tiny_sparse  # noqa: E402

from benchmarks.builders import (afmoe, gpt_decoder,  # noqa: E402
                                 sparse_moe_decoder)

G = "jit(spmd)/hvd.grad/jvp(M)/h0"
B = "jit(spmd)/hvd.grad/transpose(jvp(M))/h0"
R = ("jit(spmd)/hvd.grad/transpose(jvp(M))/hvd.grad/jvp(M)/checkpoint/"
     "rematted_computation/h0")


def _md(path: str) -> str:
    return f', metadata={{op_name="{path}" stack_frame_id=2}}'


# One module with every shape of the three rules. Layouts, a tuple shape,
# an operand comment and a backend_config are as the TPU compiler prints
# them.
TEXT = f"""HloModule jit_spmd, is_scheduled=true

%add.reducer (x.1: f32[], y.1: f32[]) -> f32[] {{
  %x.1 = f32[] parameter(0)
  %y.1 = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%x.1, %y.1){_md(G + "/hvd.norm/reduce_sum")}
}}

%fused_computation.1 (param_0.1: f32[64,128], param_1.1: bf16[64,128]) -> bf16[64,128] {{
  %param_0.1 = f32[64,128]{{1,0:T(8,128)}} parameter(0)
  %param_1.1 = bf16[64,128]{{1,0:T(8,128)(2,1)}} parameter(1)
  %constant.1 = f32[] constant(2)
  %broadcast.1 = f32[64,128]{{1,0}} broadcast(%constant.1), dimensions={{}}{_md(G + "/hvd.norm/mul")}
  %mul.1 = f32[64,128]{{1,0:T(8,128)}} multiply(%param_0.1, %broadcast.1){_md(G + "/hvd.norm/mul")}
  %bitcast.1 = f32[64,128]{{1,0}} bitcast(%mul.1){_md(G + "/hvd.norm/mul")}
  ROOT %sub.1 = bf16[64,128]{{1,0:T(8,128)(2,1)}} subtract(%bitcast.1, %param_1.1){_md(G + "/attn/hvd.rotary/sub")}
}}

%fused_computation.2 (param_0.2: f32[64,128], param_1.2: bf16[128,256]) -> bf16[64,256] {{
  %param_0.2 = f32[64,128]{{1,0}} parameter(0)
  %param_1.2 = bf16[128,256]{{1,0}} parameter(1)
  %mul.2 = f32[64,128]{{1,0}} multiply(%param_0.2, %param_0.2){_md(G + "/hvd.norm/ln1/mul")}
  %convert.2 = bf16[64,128]{{1,0}} convert(%mul.2){_md(G + "/hvd.norm/ln1/convert_element_type")}
  %convolution.2 = bf16[64,256]{{1,0}} convolution(%convert.2, %param_1.2), dim_labels=bf_io->bf{_md(G + "/attn/hvd.attn_proj/qkv/dot_general")}
  ROOT %add.2 = bf16[64,256]{{1,0}} add(%convolution.2, %convolution.2){_md(G + "/attn/hvd.attn_proj/qkv/add")}
}}

%fused_computation.3 (param_0.3: f32[64,128]) -> (f32[64], f32[64,128]) {{
  %param_0.3 = f32[64,128]{{1,0}} parameter(0)
  %constant.3 = f32[] constant(0)
  %reduce.3 = f32[64]{{0}} reduce(%param_0.3, %constant.3), dimensions={{1}}, to_apply=%add.reducer{_md("jit(spmd)/hvd.optimizer_update/reduce_sum")}
  %convert.3 = f32[64,128]{{1,0}} convert(%param_0.3)
  %dynamic-update-slice.3 = f32[64,128]{{1,0}} dynamic-update-slice(%convert.3, %param_0.3, %constant.3, %constant.3)
  ROOT %tuple.3 = (f32[64]{{0}}, f32[64,128]{{1,0}}) tuple(%reduce.3, %dynamic-update-slice.3)
}}

%fused_computation.4 (param_0.4: f32[8]) -> f32[8] {{
  %param_0.4 = f32[8]{{0}} parameter(0)
  %fusion.inner = f32[8]{{0}} fusion(%param_0.4), kind=kLoop, calls=%fused_computation.5
  ROOT %neg.4 = f32[8]{{0}} negate(%fusion.inner){_md(B + "/mlp/hvd.mlp/neg")}
}}

%fused_computation.5 (param_0.5: f32[8]) -> f32[8] {{
  %param_0.5 = f32[8]{{0}} parameter(0)
  ROOT %exp.5 = f32[8]{{0}} exponential(%param_0.5){_md(B + "/hvd.norm/exp")}
}}

%fused_computation.6 (param_0.6: f32[8]) -> f32[8] {{
  %param_0.6 = f32[8]{{0}} parameter(0)
  ROOT %bitcast.6 = f32[8]{{0}} bitcast(%param_0.6)
}}

%body.1 (arg.1: (s32[], f32[64,128])) -> (s32[], f32[64,128]) {{
  %arg.1 = (s32[]{{:T(128)}}, f32[64,128]{{1,0}}) parameter(0)
  %get-tuple-element.1 = s32[]{{:T(128)}} get-tuple-element(%arg.1), index=0
  %get-tuple-element.2 = f32[64,128]{{1,0}} get-tuple-element(%arg.1), index=1
  %gather.1 = f32[64,128]{{1,0}} fusion(%get-tuple-element.2), kind=kLoop, calls=%fused_computation.6{_md(R + "/moe/hvd.moe_ffn/while/body/gather")}
  %ragged-dot-metadata = (s32[17]{{0}}, s32[16]{{0}}) custom-call(%gather.1), custom_call_target="tpu_custom_call"{_md("ragged-dot-metadata")}
  %get-tuple-element.3 = s32[17]{{0}} get-tuple-element(%ragged-dot-metadata), index=0
  %ragged-dot-none = f32[64,128]{{1,0}} custom-call(%get-tuple-element.3, /*index=1*/%gather.1), custom_call_target="tpu_custom_call"{_md("ragged-dot-none")}, backend_config={{"custom_call_config":{{"body":"calls=%nothing"}}}}
  ROOT %tuple.1 = (s32[]{{:T(128)}}, f32[64,128]{{1,0}}) tuple(%get-tuple-element.1, %ragged-dot-none)
}}

%cond.1 (arg.2: (s32[], f32[64,128])) -> pred[] {{
  %arg.2 = (s32[]{{:T(128)}}, f32[64,128]{{1,0}}) parameter(0)
  %get-tuple-element.4 = s32[]{{:T(128)}} get-tuple-element(%arg.2), index=0
  %constant.4 = s32[]{{:T(128)}} constant(4)
  ROOT %lt.1 = pred[]{{:T(512)}} compare(%get-tuple-element.4, %constant.4), direction=LT{_md(R + "/moe/hvd.moe_ffn/while/cond/lt")}
}}

ENTRY %main.1 (p__h0__ln1__scale.1: f32[64,128], p__wte.1: bf16[128,256], x.2: bf16[64,128]) -> f32[8] {{
  %p__h0__ln1__scale.1 = f32[64,128]{{1,0:T(8,128)}} parameter(0), metadata={{op_name="p[\\'h0\\'][\\'ln1\\'][\\'scale\\']"}}
  %p__wte.1 = bf16[128,256]{{1,0}} parameter(1), metadata={{op_name="p[\\'wte\\']"}}
  %x.2 = bf16[64,128]{{1,0}} parameter(2), metadata={{op_name="x"}}
  %slice_subtract_fusion = bf16[64,128]{{1,0:T(8,128)(2,1)}} fusion(%p__h0__ln1__scale.1, %x.2), kind=kLoop, calls=%fused_computation.1{_md(G + "/attn/hvd.rotary/sub")}, backend_config={{"flag_configs":[]}}
  %copy-start.1 = (bf16[128,256]{{1,0:S(1)}}, bf16[128,256]{{1,0}}, u32[]{{:S(2)}}) copy-start(%p__wte.1)
  %bitcast.7 = (bf16[128,256]{{1,0:S(1)}}, bf16[128,256]{{1,0}}, u32[]{{:S(2)}}) bitcast(%copy-start.1)
  %copy-done.1 = bf16[128,256]{{1,0:S(1)}} copy-done(%bitcast.7)
  %convolution_add_fusion = bf16[64,256]{{1,0}} fusion(%p__h0__ln1__scale.1, %copy-done.1), kind=kOutput, calls=%fused_computation.2{_md(G + "/attn/hvd.attn_proj/qkv/add")}
  %copy-start.2 = (bf16[64,256]{{1,0}}, bf16[64,256]{{1,0:S(1)}}, u32[]{{:S(2)}}) copy-start(%convolution_add_fusion)
  %copy-done.2 = bf16[64,256]{{1,0}} copy-done(%copy-start.2)
  %multiply_reduce_fusion = (f32[64]{{0}}, f32[64,128]{{1,0}}) fusion(%p__h0__ln1__scale.1), kind=kLoop, calls=%fused_computation.3{_md("jit(spmd)/hvd.optimizer_update/reduce_sum")}
  %get-tuple-element.5 = f32[64,128]{{1,0}} get-tuple-element(%multiply_reduce_fusion), index=1{_md("jit(spmd)/add")}
  %copy-start.3 = (f32[64,128]{{1,0}}, f32[64,128]{{1,0:S(1)}}, u32[]{{:S(2)}}) copy-start(%get-tuple-element.5)
  %copy-done.3 = f32[64,128]{{1,0}} copy-done(%copy-start.3)
  %constant.7 = s32[]{{:T(128)}} constant(0)
  %tuple.7 = (s32[]{{:T(128)}}, f32[64,128]{{1,0}}) tuple(%constant.7, %copy-done.3)
  %while.1 = (s32[]{{:T(128)}}, f32[64,128]{{1,0}}) while(%tuple.7), condition=%cond.1, body=%body.1{_md(R + "/moe/hvd.moe_ffn/while")}
  %iota.1 = f32[8]{{0}} iota(), iota_dimension=0
  %nested_fusion = f32[8]{{0}} fusion(%iota.1), kind=kLoop, calls=%fused_computation.4{_md(B + "/mlp/hvd.mlp/neg")}
  %residual.1 = f32[8]{{0}} add(%nested_fusion, %nested_fusion){_md(B + "/add_any")}
  %all-reduce.1 = f32[8]{{0}} all-reduce(%residual.1), replica_groups={{}}, to_apply=%add.reducer{_md("jit(spmd)/psum")}
  ROOT %copy.9 = f32[8]{{0}} copy(%all-reduce.1)
}}
"""

NORM_F, ROTARY_F = ("hvd.norm", "forward"), ("hvd.rotary", "forward")
PROJ_F, OPT = ("hvd.attn_proj", "forward"), ("hvd.optimizer_update",
                                             "forward")
MOE_R = ("hvd.moe_ffn", "remat")
UNOWNED = (ho.UNOWNED, ho.FORWARD)


@pytest.fixture(scope="module")
def owned():
    return ho.owners(TEXT)


RULES = {
    # a loop fusion of two owners, by the bytes of each result: the norm's
    # f32 multiply 32 KB against the rotation's bf16 subtract 16 KB; the
    # root's path alone would have said rotary
    "loop_fusion_by_bytes": ("slice_subtract_fusion",
                             {NORM_F: 2 / 3, ROTARY_F: 1 / 3}),
    # a matmul fusion goes whole to the dot's owner, norm prologue and all
    "matmul_fusion_whole": ("convolution_add_fusion", {PROJ_F: 1.0}),
    # no op_name: the producer of the first operand, through a bitcast ...
    "copy_done_through_bitcast": ("copy-done.1", {PROJ_F: 1.0}),
    # ... and, from a parameter (whose op_name is its argument's name),
    # down the first users
    "copy_start_of_a_parameter": ("copy-start.1", {PROJ_F: 1.0}),
    "parameter_as_its_reader": ("p__wte.1", {PROJ_F: 1.0}),
    # the producer's owners are inherited with their weights
    "copy_of_a_fusions_result": ("copy-done.2", {PROJ_F: 1.0}),
    # a reducer's to_apply weighs nothing; an inner instruction with no
    # path and none to inherit goes by the fusion's own (its root's)
    "reducer_left_out_inner_pathless": ("multiply_reduce_fusion",
                                        {OPT: 1.0}),
    # a path with no hvd.* name is a path: apply_updates' add stays unowned
    # and so does the copy of its result
    "unowned_add": ("get-tuple-element.5", {UNOWNED: 1.0}),
    "unowned_copy": ("copy-done.3", {UNOWNED: 1.0}),
    # a while and the instructions of its body are instructions like any
    # other; the rematerialised forward is a direction of its own
    "while": ("while.1", {MOE_R: 1.0}),
    "while_body_fusion_of_bitcasts": ("gather.1", {MOE_R: 1.0}),
    "while_cond": ("lt.1", {MOE_R: 1.0}),
    # an op_name the compiler made up is no path: owned as what it reads
    "compiler_made_name": ("ragged-dot-metadata", {MOE_R: 1.0}),
    "compiler_made_name_through_gte": ("ragged-dot-none", {MOE_R: 1.0}),
    # nested calls are followed: 32 B of exp under hvd.norm, 32 B of neg
    # under hvd.mlp, both backward
    "nested_call": ("nested_fusion", {("hvd.norm", "backward"): 0.5,
                                      ("hvd.mlp", "backward"): 0.5}),
    # under hvd.grad with no inner owner
    "grad_unowned": ("residual.1", {("hvd.grad", "backward"): 1.0}),
    "plain_unowned": ("all-reduce.1", {UNOWNED: 1.0}),
    "pathless_takes_its_producers": ("copy.9", {UNOWNED: 1.0}),
    "no_producer_first_user": ("iota.1", {("hvd.norm", "backward"): 0.5,
                                          ("hvd.mlp", "backward"): 0.5}),
    # inside a fused computation too
    "inner_instruction": ("mul.1", {NORM_F: 1.0}),
    "inner_parameter": ("param_0.5", {("hvd.norm", "backward"): 1.0}),
}


@pytest.mark.parametrize("case", sorted(RULES))
def test_rule(case, owned):
    name, expect = RULES[case]
    assert owned[name] == pytest.approx(expect)


def test_every_instruction_is_in_the_map_and_sums_to_one(owned):
    names = set(re.findall(r"^\s+(?:ROOT )?%([\w.\-]+) = ", TEXT, re.M))
    assert len(names) == 63 and set(owned) == names
    for name, shares in owned.items():
        assert sum(shares.values()) == pytest.approx(1.0), name
        assert all(w > 0 for w in shares.values()), name


def test_mixed_is_more_than_one_owner(owned):
    assert ho.mixed(owned["slice_subtract_fusion"])
    assert ho.mixed(owned["nested_fusion"])
    assert not ho.mixed(owned["convolution_add_fusion"])
    # two directions of one owner rest on no convention
    assert not ho.mixed({NORM_F: 0.5, ("hvd.norm", "backward"): 0.5})


@pytest.mark.parametrize("path, key", [
    (G + "/attn/hvd.flash_attention/hvd.flash_window/hvd_flash_fwd_win",
     ("hvd.flash_window", "forward")),           # the innermost name
    (B + "/jvp(hvd.lm_head_loss)/dot_general",
     ("hvd.lm_head_loss", "backward")),
    (R + "/attn/hvd.norm/mul", ("hvd.norm", "remat")),   # not backward
    (R.replace("rematted_computation/", "") + "/attn/hvd.norm/mul",
     ("hvd.norm", "backward")),     # the checkpoint's real backward
    (G + "/add", ("hvd.grad", "forward")),
    ("jit(spmd)/hvd.allreduce_grads/hvd.bucket_pack/concatenate",
     ("hvd.bucket_pack", "forward")),
    ("jit(spmd)/add", UNOWNED),
    ("", UNOWNED)])
def test_key_of(path, key):
    assert ho.key_of(path) == key


@pytest.mark.parametrize("shape, nbytes", [
    ("f32[64,128]{1,0:T(8,128)}", 32768),
    ("bf16[16,1024,2304]{2,1,0:T(8,128)(2,1)S(1)}", 2 * 16 * 1024 * 2304),
    ("pred[7]{0}", 7), ("s32[]{:T(128)}", 4), ("f8e4m3fn[4,4]{1,0}", 16),
    ("(f32[8]{0}, (bf16[2,2]{1,0}, u32[]{:S(2)}), token[])", 32 + 8 + 4),
    ("s4[8]{0}", 4)])
def test_result_bytes(shape, nbytes):
    assert ho.result_bytes(shape) == nbytes


@pytest.mark.parametrize("text, name", [
    ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop", "fusion.7"),
    ("  ROOT %copy-done.3 = f32[8] copy-done(%c)", "copy-done.3"),
    ("hvd_flash_fwd.3 = (bf16[8]) custom-call()", "hvd_flash_fwd.3"),
    ("", "")])
def test_instruction_name(text, name):
    assert ho.instruction_name(text) == name


def test_by_owner_reduces_events_and_keeps_the_strangers(owned):
    """What an operator does with a ``hvd.profile_window`` trace: the
    events' own texts and seconds against the map."""
    events = [("%slice_subtract_fusion = bf16[64,128] fusion(...)", 3.0),
              ("%convolution_add_fusion = bf16[64,256] fusion(...)", 2.0),
              ("%copy-done.1 = bf16[128,256] copy-done(...)", 0.5),
              ("%copy-done.3 = f32[64,128] copy-done(...)", 0.25),
              ("%fusion.99 = f32[8] fusion(...)", 1.0),
              ("%fusion.99 = f32[8] fusion(...)", 1.0)]
    totals, missing = ho.by_owner(events, owned)
    assert totals == pytest.approx({NORM_F: 2.0, ROTARY_F: 1.0,
                                    PROJ_F: 2.5, UNOWNED: 0.25})
    assert missing == {"fusion.99": 2.0}


def test_a_call_that_calls_itself_ends():
    text = ("%f.1 (p.1: f32[8]) -> f32[8] {\n"
            "  %p.1 = f32[8]{0} parameter(0)\n"
            "  ROOT %g.1 = f32[8]{0} fusion(%p.1), kind=kLoop, calls=%f.1\n"
            "}\n")
    assert ho.owners(text) == {"p.1": {UNOWNED: 1.0}, "g.1": {UNOWNED: 1.0}}


# -- the compiled text of the three tiny steps --------------------------------

STEPS = {"gpt": (gpt_decoder, tiny), "afmoe": (afmoe, tiny_afmoe),
         "sparse": (sparse_moe_decoder, tiny_sparse)}


@pytest.fixture(scope="module")
def tiny_texts():
    try:
        texts = {}
        for name, (builder, module) in STEPS.items():
            session = builder.build(module.CONFIG, module.JOB,
                                    jax.devices()[:1])
            texts[name] = session.lower(
                session.abstract_args()).compile().as_text()
        yield texts
    finally:
        hvd.shutdown()     # the builder owns init/shutdown: hand the
        hvd.init()         # other tests their mesh back


@pytest.fixture(scope="module")
def tiny_owners(tiny_texts):
    return {step: ho.owners(text) for step, text in tiny_texts.items()}


@pytest.mark.parametrize("step", sorted(STEPS))
def test_every_instruction_of_a_tiny_step_is_in_the_map(step, tiny_texts,
                                                        tiny_owners):
    text, owned = tiny_texts[step], tiny_owners[step]
    names = set(re.findall(r"^\s+(?:ROOT )?%([\w.\-]+) = ", text, re.M))
    assert len(names) > 5000 and names == set(owned)
    for name, shares in owned.items():
        assert sum(shares.values()) == pytest.approx(1.0), name


@pytest.mark.parametrize("step", sorted(STEPS))
def test_a_tiny_steps_owners_are_the_vocabulary(step, tiny_owners):
    owned = tiny_owners[step]
    owners = {owner for shares in owned.values() for owner, _ in shares}
    assert owners <= set(DEVICE_SCOPES) | {ho.UNOWNED}
    assert {"hvd.grad", "hvd.norm", "hvd.attn_proj", "hvd.embed",
            "hvd.optimizer_update", "hvd.lm_head_loss"} <= owners
    directions = {d for shares in owned.values() for _, d in shares}
    # nn.remat is the mixture decoder's; the GPT-2 cells run without
    assert directions == {ho.FORWARD, ho.BACKWARD} | (
        set() if step == "gpt" else {ho.REMAT})


@pytest.mark.parametrize("step", sorted(STEPS))
def test_fusions_of_a_tiny_step_are_read_by_what_is_inside(step, tiny_texts,
                                                           tiny_owners):
    """PR 24's finding and its cure: some fusion's root carries another
    scope than the instructions it holds, and AdamW's arithmetic sits in
    fusions whose root is ``apply_updates``' unscoped add."""
    program = ho.parse(tiny_texts[step])
    owned = tiny_owners[step]
    entry = list(program.values())[-1]
    fusions = [i for i in entry if i.calls]
    assert fusions
    assert any(ho.mixed(owned[i.name]) for i in fusions)
    by_root_unowned = [i for i in fusions
                       if ho.key_of(i.path)[0] == ho.UNOWNED]
    assert any("hvd.optimizer_update" in {o for o, _ in owned[i.name]}
               for i in by_root_unowned)


# -- scripts/scope_bytes.py: the same text, counted by bytes -------------------

BYTES_TEXT = f"""HloModule jit_spmd, is_scheduled=true

%fused_mm (p.0: bf16[8,16], p.1: bf16[16,4]) -> bf16[8,4] {{
  %p.0 = bf16[8,16]{{1,0}} parameter(0)
  %p.1 = bf16[16,4]{{1,0}} parameter(1)
  ROOT %dot.1 = bf16[8,4]{{1,0}} dot(%p.0, %p.1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}{_md(G + "/hvd.attn_proj/dot_general")}
}}

%fused_mul (p.2: bf16[8,16]) -> f32[8,16] {{
  %p.2 = bf16[8,16]{{1,0}} parameter(0)
  %c.2 = f32[8,16]{{1,0}} convert(%p.2){_md(G + "/hvd.diff_attention/convert")}
  ROOT %m.2 = f32[8,16]{{1,0}} multiply(%c.2, %c.2){_md(G + "/hvd.diff_attention/mul")}
}}

ENTRY %main (a: bf16[8,16], w: bf16[16,4]) -> (bf16[8,4], bf16[8,32]) {{
  %a = bf16[8,16]{{1,0:T(8,128)(2,1)}} parameter(0)
  %w = bf16[16,4]{{1,0}} parameter(1)
  %view = bf16[128]{{0}} bitcast(%a)
  %wide = f32[8,16]{{1,0:T(8,128)}} fusion(%a), kind=kLoop, calls=%fused_mul{_md(G + "/hvd.diff_attention/mul")}
  %again = f32[8,16]{{1,0}} copy(%wide){_md(R + "/hvd.diff_attention/copy")}
  %mm = bf16[8,4]{{1,0}} fusion(%a, %w), kind=kOutput, calls=%fused_mm{_md(G + "/hvd.attn_proj/dot_general")}
  %kernel = bf16[8,32]{{1,0}} custom-call(%a), custom_call_target="tpu_custom_call"{_md(B + "/hvd.diff_attention/hvd_diff_lay_fwd/pallas_call")}
  ROOT %out = (bf16[8,4]{{1,0}}, bf16[8,32]{{1,0}}) tuple(%mm, %kernel)
}}
"""


def test_scope_bytes_counts_operands_and_results_by_scope():
    """``scripts/scope_bytes.py``: an entry instruction moves its operands'
    and its result's bytes, goes to its own path's innermost scope and
    direction, and matmul fusions and custom calls stand apart."""
    scripts = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts")
    sys.path.insert(0, scripts)
    try:
        import scope_bytes as sb
    finally:
        sys.path.remove(scripts)
    rows = sb.moved(BYTES_TEXT)
    assert [r[0].name for r in rows] == ["wide", "again", "mm", "kernel"]
    table = sb.by_scope(rows)
    diff = "hvd.diff_attention"
    assert table[(diff, ho.FORWARD)][sb.ELEMENTWISE] == [1, 256 + 512]
    assert table[(diff, ho.REMAT)][sb.ELEMENTWISE] == [1, 512 + 512]
    assert table[(diff, ho.BACKWARD)][sb.KERNEL] == [1, 256 + 512]
    assert table[("hvd.attn_proj", ho.FORWARD)][sb.MATMUL] == [
        1, 256 + 128 + 64]
    assert sb.ELEMENTWISE not in table[("hvd.attn_proj", ho.FORWARD)]
