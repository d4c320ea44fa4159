"""`analysis.hlo_audit` + `hvt-audit` — the compiled-program auditor
(ISSUE 9 layer 2).

Parser units run over handcrafted fixtures of BOTH text dialects jax
emits (lowered StableHLO, post-optimization HLO), then the integration
tests audit real lowered trainer steps through `analysis.step_probe` —
the same plumbing the migrated perf-path tests ride. The
CLI subprocess tests pin the exit-code contract (0 clean / 1 violation
/ 2 usage) and are the tier-1 gate for the canonical K=4 + int8 step:
`hvt-audit step` must fail loudly when the HVT_OVERLAP_REDUCTION or
compression invariants are off.
"""

import os
import subprocess
import sys
import textwrap

import pytest

from horovod_tpu.analysis import hlo_audit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# --- fixture programs -------------------------------------------------------

STABLEHLO_SAMPLE = textwrap.dedent("""\
    module @jit_train_step {
      func.func public @main(%arg0: tensor<2410xf32>) -> tensor<2410xf32> {
        %0 = stablehlo.while ... {
          %w = stablehlo.add %arg0, %arg0 : tensor<2410xf32>
        }
        %144 = "stablehlo.all_gather"(%143) <{all_gather_dim = 0 : i64,
            channel_handle = #stablehlo.channel_handle<handle = 1, type = 1>
        }> : (tensor<301xi8>) -> tensor<8x301xi8>
        %146 = "stablehlo.all_gather"(%145) <{all_gather_dim = 0 : i64
        }> : (tensor<f32>) -> tensor<8xf32>
        %177 = "stablehlo.all_reduce"(%112) <{channel_handle =
            #stablehlo.channel_handle<handle = 3, type = 1>}> ({
        ^bb0(%a: tensor<f32>, %b: tensor<f32>):
          %s = stablehlo.add %a, %b : tensor<f32>
          stablehlo.return %s : tensor<f32>
        }) : (tensor<f32>) -> tensor<f32>
        %180 = "stablehlo.all_reduce"(%113) <{channel_handle =
            #stablehlo.channel_handle<handle = 4, type = 1>}> ({
        ^bb0(%a: tensor<f32>, %b: tensor<f32>):
          %s = stablehlo.add %a, %b : tensor<f32>
          stablehlo.return %s : tensor<f32>
        }) : (tensor<2410xbf16>) -> tensor<2410xbf16>
      }
    }
""")

HLO_SAMPLE = textwrap.dedent("""\
    HloModule jit_train_step, is_scheduled=true, input_output_alias={ {0}: (0, {}, may-alias), {1}: (2, {}, may-alias) }, entry_computation_layout={...}

    %region_17.445 (x: f32[], y: f32[]) -> f32[] {
      ROOT %add = f32[] add(f32[] %x, f32[] %y)
    }

    ENTRY %main {
      %while.19 = (s32[], f32[2410]{0}) while((s32[], f32[2410]{0}) %tuple.5), condition=%cond, body=%body
      %all-reduce.6 = f32[2410]{0} all-reduce(f32[2410]{0} %convert_fusion), channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%region_17.445
      %all-reduce.3 = f32[] all-reduce(f32[] %add_fusion), channel_id=2, to_apply=%region_17.445
      %ag = (s8[8,2410]{1,0}, s8[8,2410]{1,0}) all-gather-start(s8[2410]{0} %q), channel_id=3, dimensions={0}
      %ag-d = s8[8,2410]{1,0} all-gather-done((s8[8,2410]{1,0}, s8[8,2410]{1,0}) %ag)
      %scales = f32[8]{0} all-gather(f32[] %scale), channel_id=4, dimensions={0}
      %use = f32[2410]{0} fusion(f32[2410]{0} %all-reduce.6), kind=kLoop
    }
""")


class TestParsers:
    def test_stablehlo_ops_and_order(self):
        ops = hlo_audit.collective_ops(STABLEHLO_SAMPLE)
        assert [(o.kind, o.dtype, o.shape) for o in ops] == [
            ("all-gather", "i8", (8, 301)),
            ("all-gather", "f32", (8,)),
            ("all-reduce", "f32", ()),
            ("all-reduce", "bf16", (2410,)),
        ]
        assert [o.index for o in ops] == [0, 1, 2, 3]

    def test_hlo_ops_skip_done_and_uses(self):
        """The -done completion and operand USES of a collective's value
        must not double-count; -start counts once; s8 canonicalizes to
        i8; tuple result types count the op once."""
        ops = hlo_audit.collective_ops(HLO_SAMPLE)
        assert [(o.kind, o.dtype, o.shape) for o in ops] == [
            ("all-reduce", "f32", (2410,)),
            ("all-reduce", "f32", ()),
            ("all-gather", "i8", (8, 2410)),
            ("all-gather", "f32", (8,)),
        ]

    def test_gradient_discrimination(self):
        """The shared discrimination: scalar all-reduces (metric
        means) and rank-1 gathers (quantized-wire per-bucket scales) are
        NOT gradient traffic; non-scalar all-reduces and rank>=2 payload
        gathers are."""
        for sample in (STABLEHLO_SAMPLE, HLO_SAMPLE):
            grads = hlo_audit.gradient_reductions(sample)
            assert len(grads) == 2
            kinds = {(o.kind, o.rank) for o in grads}
            assert ("all-gather", 2) in kinds
            assert all(
                not (o.kind == "all-gather" and o.rank < 2) for o in grads
            )
            assert all(not o.scalar for o in grads)

    def test_while_count_both_dialects(self):
        assert hlo_audit.while_count(STABLEHLO_SAMPLE) == 1
        assert hlo_audit.while_count(HLO_SAMPLE) == 1

    def test_while_bodies_follow_callees_and_filter_by_scope(self):
        text = textwrap.dedent("""\
            HloModule jit_step

            %add (x: f32[], y: f32[]) -> f32[] {
              ROOT %s = f32[] add(f32[] %x, f32[] %y)
            }

            %fused (p: f32[8,4]) -> f32[8,4] {
              ROOT %n = f32[8,4]{1,0} negate(f32[8,4]{1,0} %p)
            }

            %head_body (c: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
              %f = f32[8,4]{1,0} fusion(f32[8,4]{1,0} %g), kind=kLoop, calls=%fused
            }

            %other_body (c: (s32[], f32[8])) -> (s32[], f32[8]) {
              %ar = f32[8]{0} all-reduce(f32[8]{0} %g), channel_id=1, to_apply=%add
            }

            ENTRY %main {
              %w.1 = (s32[], f32[8,4]{1,0}) while(%t), condition=%c, body=%head_body, metadata={op_name="jit(step)/hvt.head_ce/while"}
              %w.2 = (s32[], f32[8]{0}) while(%u), condition=%c, body=%other_body, metadata={op_name="jit(step)/scan/while"}
            }
        """)
        head, = hlo_audit.while_bodies(text, "hvt.head_ce")
        assert "calls=%fused" in head and "negate" in head  # the callee too
        assert not hlo_audit.collective_ops(head)
        both = hlo_audit.while_bodies(text)
        assert len(both) == 2
        assert [o.kind for o in hlo_audit.collective_ops(both[1])] == [
            "all-reduce"]

    def test_donated_args_hlo_header(self):
        assert hlo_audit.donated_args(HLO_SAMPLE) == [0, 2]

    def test_donated_args_stablehlo_markers(self):
        text = (
            'func.func public @main(%arg0: tensor<4xf32> '
            '{tf.aliasing_output = 0 : i32}, %arg1: tensor<4xf32>, '
            '%arg2: tensor<4xf32> {jax.buffer_donor = true}) '
            "stablehlo.add"
        )
        assert len(hlo_audit.donated_args(text)) == 2

    def test_wire_dtype_aliases(self):
        assert hlo_audit.wire_dtype("int8") == "i8"
        assert hlo_audit.wire_dtype("fp8") == "f8e4m3"
        assert hlo_audit.wire_dtype("BF16") == "bf16"
        assert hlo_audit.wire_dtype("none") == "f32"
        with pytest.raises(ValueError, match="unknown wire"):
            hlo_audit.wire_dtype("int4")


# How XLA:TPU writes one cross-chip sum, cut to what tells the three ways
# apart (from compiles of the data=4 step for a described v5e:2x2, PR 30).
_SUM = ("all-reduce(%p), channel_id=7, replica_groups=[1,4]<=[4], "
        "to_apply=%add")
_SCALAR = "%loss = f32[] all-reduce(%l), channel_id=2, to_apply=%add"

PLAIN_ALL_REDUCE = f"""\
HloModule jit_train_step, is_scheduled=true

ENTRY %main.1_spmd (p: bf16[2048,8192]) -> bf16[2048,8192] {{
  %p = bf16[2048,8192]{{1,0}} parameter(0)
  {_SCALAR}
  %all-reduce.30 = (bf16[2048,8192]{{1,0:T(8,128)(2,1)}}, f32[2048]{{0:T(1024)}}) {_SUM}
  ROOT %use = bf16[2048,8192]{{1,0}} fusion(%all-reduce.30), kind=kLoop, calls=%fc
}}
"""

START_DONE_PAIR = f"""\
HloModule jit_train_step, is_scheduled=true

ENTRY %main.1_spmd (p: bf16[2048,8192]) -> bf16[2048,8192] {{
  %p = bf16[2048,8192]{{1,0}} parameter(0)
  %all-reduce-start.1 = bf16[2048,8192]{{1,0}} all-reduce-start(%p), channel_id=7, to_apply=%add
  %between = bf16[2,2048,8192]{{2,1,0}} fusion(%q), kind=kOutput, calls=%fc
  ROOT %all-reduce-done.1 = bf16[2048,8192]{{1,0}} all-reduce-done(%all-reduce-start.1)
}}
"""

ASYNC_COLLECTIVE_FUSION = f"""\
HloModule jit_train_step, is_scheduled=true

%fused_computation.865 (param_0.1: bf16[2048,8192]) -> (bf16[2048,8192], u32[]) {{
  %param_0.1 = bf16[2048,8192]{{1,0}} parameter(0)
  %all-reduce.147 = bf16[2048,8192]{{1,0:T(8,128)(2,1)}} {_SUM}
}}

%async_collective_fusion.668 (param_0.2: bf16[2048,8192], param_1.2: bf16[2,2048,2048]) -> (bf16[16,128,2048], bf16[2048,8192]) {{
  %convolution.495 = bf16[16,128,2048,1]{{2,1,0,3}} convolution(%a, %b), window={{size=2x1}}
  %all-reduce.149 = bf16[2048,8192]{{1,0:T(8,128)(2,1)S(1)}} {_SUM}
}}

%async_collective_fusion.669 (param_0.3: bf16[2048,8192]) -> (bf16[16,128,2048], bf16[2048,8192]) {{
  %all-reduce.150 = bf16[2048,8192]{{1,0:T(8,128)(2,1)S(1)}} {_SUM}
}}

%fused_computation.867 (param_0.4: bf16[2048,8192]) -> bf16[2048,8192] {{
  %all-reduce.151 = bf16[2048,8192]{{1,0:T(8,128)(2,1)}} {_SUM}
}}

ENTRY %main.1_spmd (p: bf16[2048,8192]) -> bf16[2048,8192] {{
  %p = bf16[2048,8192]{{1,0}} parameter(0)
  %async-collective-start.2 = (bf16[2048,8192]{{1,0}}, u32[]) fusion(%p), kind=kCustom, calls=%fused_computation.865
  %fusion.668 = (bf16[16,128,2048]{{2,1,0}}, bf16[2048,8192]{{1,0}}) fusion(%gte.1, %x), kind=kOutput, calls=%async_collective_fusion.668
  %fusion.669 = (bf16[16,128,2048]{{2,1,0}}, bf16[2048,8192]{{1,0}}) fusion(%gte.2), kind=kOutput, calls=%async_collective_fusion.669
  %async-collective-done.2 = bf16[2048,8192]{{1,0}} fusion(%gte.3), kind=kCustom, calls=%fused_computation.867
  %psum.7 = f32[2048,50257]{{0,1:T(8,128)}} all-reduce(%dw), channel_id=1, to_apply=%add
  ROOT %out = bf16[2048,8192]{{1,0}} fusion(%async-collective-done.2, %psum.7), kind=kLoop, calls=%fc
}}
"""


class TestReductionSchedule:
    @pytest.mark.parametrize("text,expected,share", [
        pytest.param(  # one combined sum: every byte of the tuple counts
            PLAIN_ALL_REDUCE,
            [("bf16", 2048 * 8192 * 2 + 2048 * 4, False)], 0.0,
            id="plain-all-reduce"),
        pytest.param(
            START_DONE_PAIR, [("bf16", 2048 * 8192 * 2, True)], 1.0,
            id="start-done-pair"),
        pytest.param(  # start, two steps and done restate ONE sum
            ASYNC_COLLECTIVE_FUSION,
            [("bf16", 2048 * 8192 * 2, True),
             ("f32", 2048 * 50257 * 4, False)],
            2048 * 8192 * 2 / (2048 * 8192 * 2 + 2048 * 50257 * 4),
            id="async-collective-fusion"),
    ])
    def test_asynchronous_and_synchronous_sums_are_told_apart(
            self, text, expected, share):
        found = hlo_audit.reduction_schedule(text)
        assert [
            (r.dtype, r.nbytes, r.asynchronous) for r in found
        ] == expected
        assert hlo_audit.asynchronous_share(found) == pytest.approx(share)

    def test_a_program_that_sums_nothing_has_no_share(self):
        gathers = hlo_audit.reduction_schedule(HLO_SAMPLE.replace(
            "all-reduce", "add"))
        assert [r for r in gathers if r.reduces] == []
        assert hlo_audit.asynchronous_share(gathers) is None
        assert hlo_audit.asynchronous_share([]) is None


# The same three texts with the metadata XLA writes: the collective's own
# `op_name` on every restatement, the compute's on what a host computes,
# and a first instruction (a convert) under no scope.
_JIT = "jit(train_step)/"
_MLP = "transpose(jvp(TransformerLM))/Block_3/Block_3._mlp/mlp_down"


def _meta(op_name):
    return f', metadata={{op_name="{_JIT}{op_name}" source_line=7}}'


NAMED_ASYNC_COLLECTIVE_FUSION = (
    ASYNC_COLLECTIVE_FUSION
    .replace(f"{_SUM}\n", _SUM + _meta(f"{_MLP}/dot_general") + "\n")
    .replace("  %convolution.495 = ",
             "  %convert.9 = bf16[2048]{0} convert(%c)"
             + _meta("convert_element_type") + "\n"
             "  %convolution.495 = ")
    .replace("window={size=2x1}",
             "window={size=2x1}" + _meta(f"{_MLP}/dot_general"))
    .replace("  %all-reduce.150 = ",
             "  %multiply.3 = f32[2048,8192]{1,0} multiply(%m, %v)"
             + _meta("hvt.optimizer/mul") + "\n"
             "  %add.5 = f32[2048,8192]{1,0} add(%multiply.3, %g)"
             + _meta(f"{_MLP}/add_any") + "\n"
             "  %all-reduce.150 = ")
    .replace("channel_id=1, to_apply=%add",
             "channel_id=1, to_apply=%add" + _meta(
                 "transpose(jvp(TransformerLM))/lm_head.fused_loss/"
                 "hvt.head_ce/psum"))
)

ALL_GATHER = """\
HloModule jit_train_step, is_scheduled=true

ENTRY %main.1_spmd (p: bf16[512,8192]) -> bf16[2048,8192] {
  %p = bf16[512,8192]{1,0} parameter(0)
  %ag = (bf16[512,8192]{1,0}, bf16[2048,8192]{1,0}) all-gather-start(%p), channel_id=3, dimensions={0}, metadata={op_name="jit(train_step)/hvt.optimizer/all_gather"}
  ROOT %ag-d = bf16[2048,8192]{1,0} all-gather-done((bf16[512,8192]{1,0}, bf16[2048,8192]{1,0}) %ag)
}
"""


class TestReductionTable:
    """What `reduction_schedule`'s rows say beyond bytes and a boolean:
    whose sum it is and which instructions carry it (PR 37)."""

    def test_an_asynchronous_sum_names_its_start_hosts_and_done(self):
        row, psum = hlo_audit.reduction_schedule(
            NAMED_ASYNC_COLLECTIVE_FUSION)
        assert (row.channel, row.asynchronous) == (7, True)
        assert (row.start, row.done) == (
            "async-collective-start.2", "async-collective-done.2")
        assert row.scope == _MLP
        # `fusion.668`: what its convolution names, not its first
        # instruction (a convert under no scope); `fusion.669`: a program
        # scope wins over what more instructions share.
        assert row.hosts == (
            hlo_audit.ReductionHost("fusion.668", _MLP),
            hlo_audit.ReductionHost("fusion.669", "hvt.optimizer"))
        # The synchronous sum names itself, twice, and has no hosts.
        assert (psum.start, psum.done, psum.hosts) == ("psum.7", "psum.7", ())
        assert (psum.channel, psum.asynchronous) == (1, False)
        assert psum.scope.endswith("lm_head.fused_loss/hvt.head_ce")

    def test_a_start_done_pair_names_both(self):
        row, = hlo_audit.reduction_schedule(START_DONE_PAIR)
        assert (row.start, row.done, row.hosts) == (
            "all-reduce-start.1", "all-reduce-done.1", ())

    def test_an_all_gather_is_a_row_and_no_reduction(self):
        row, = hlo_audit.reduction_schedule(ALL_GATHER)
        assert (row.kind, row.dtype, row.shape) == (
            "all-gather", "bf16", (2048, 8192))
        assert row.nbytes == 2048 * 8192 * 2  # what arrives, not the pair
        assert (row.start, row.done, row.asynchronous) == ("ag", "ag-d", True)
        assert row.scope == "hvt.optimizer" and not row.reduces
        assert hlo_audit.asynchronous_share([row]) is None

    @pytest.mark.parametrize("text", [
        PLAIN_ALL_REDUCE, START_DONE_PAIR, ASYNC_COLLECTIVE_FUSION])
    def test_a_text_without_metadata_gives_empty_scopes(self, text):
        rows = hlo_audit.reduction_schedule(text)
        assert rows and all(r.scope == "" for r in rows)
        assert all(h.host_scope == "" for r in rows for h in r.hosts)
        assert all(r.start and r.done for r in rows)

    @pytest.mark.parametrize("op_name,path", [
        ("jit(train_step)/hvt.optimizer/add", "hvt.optimizer"),
        ("pjit(step)/jvp(M)/Block_1/qkv/dot_general", "jvp(M)/Block_1/qkv"),
        ("jit(train_step)/add", ""),
        ("reduce_sum", ""),
    ])
    def test_scope_path(self, op_name, path):
        assert hlo_audit.scope_path(op_name) == path


class TestExpectations:
    def test_parse_grammar(self):
        e = hlo_audit.ProgramExpectation.parse(
            "one-reduction,wire=int8,donates=2"
        )
        assert e.gradient_reductions == 1
        assert e.wire == "int8"
        assert e.min_donated == 2
        e2 = hlo_audit.ProgramExpectation.parse(
            "reductions=3,max-reductions=4,no-collectives"
        )
        assert e2.gradient_reductions == 3
        assert e2.max_gradient_reductions == 4
        assert e2.no_explicit_collectives

    def test_parse_rejects_unknown_token(self):
        with pytest.raises(ValueError, match="unknown expectation"):
            hlo_audit.ProgramExpectation.parse("one-reduction,bogus=1")
        with pytest.raises(ValueError, match="unknown wire"):
            hlo_audit.ProgramExpectation.parse("wire=int4")

    def test_parse_scatter_tokens(self):
        e = hlo_audit.ProgramExpectation.parse("scatter-reduction")
        assert e.scatter_mode and e.scatter_reductions is None
        e2 = hlo_audit.ProgramExpectation.parse("scatters=2,wire=bf16")
        assert e2.scatter_mode and e2.scatter_reductions == 2
        assert e2.wire == "bf16"

    def test_scatter_mode_forbids_full_payload_all_reduce(self):
        """HLO_SAMPLE carries a gradient-shaped f32 all-reduce — in
        scatter mode that is THE violation (the reduction must lower
        into the sharded update's layout), reported alongside the
        missing scatter ops."""
        with pytest.raises(hlo_audit.ProgramAuditError) as e:
            hlo_audit.assert_program(HLO_SAMPLE, "scatter-reduction")
        msg = str(e.value)
        assert "forbids full-payload all-reduce" in msg
        assert "expected scatter-form" in msg

    def test_scatter_reductions_discrimination(self):
        """reduce-scatters and rank >= 2 all-to-alls count; all-gathers
        (param reassembly) and scalar ops never do — both dialects."""
        stablehlo = (
            '%0 = "stablehlo.reduce_scatter"(%a) <{scatter_dimension = 0'
            ' : i64}> : (tensor<2400xf32>) -> tensor<300xf32>\n'
            '%1 = "stablehlo.all_to_all"(%b) <{split_count = 8 : i64}> :'
            " (tensor<8x301xi8>) -> tensor<8x301xi8>\n"
            '%2 = "stablehlo.all_gather"(%c) <{all_gather_dim = 0 : i64'
            "}> : (tensor<301xi8>) -> tensor<8x301xi8>\n"
        )
        ops = hlo_audit.scatter_reductions(stablehlo)
        assert [(o.kind, o.dtype) for o in ops] == [
            ("reduce-scatter", "f32"), ("all-to-all", "i8"),
        ]
        hlo = (
            "ENTRY %main {\n"
            "  %rs = f32[300]{0} reduce-scatter(f32[2400]{0} %g), "
            "channel_id=1, dimensions={0}\n"
            "  %aa = s8[8,301]{1,0} all-to-all(s8[8,301]{1,0} %q), "
            "channel_id=2\n"
            "}\n"
        )
        ops2 = hlo_audit.scatter_reductions(hlo)
        assert [(o.kind, o.dtype) for o in ops2] == [
            ("reduce-scatter", "f32"), ("all-to-all", "i8"),
        ]

    def test_parse_alltoalls_token(self):
        e = hlo_audit.ProgramExpectation.parse("alltoalls=2,wire=f32")
        assert e.alltoalls == 2 and e.wire == "f32"

    def test_payload_alltoalls_discrimination_both_dialects(self):
        """Rank >= 2 all-to-alls count (dispatch/combine payloads, the
        quantized wire's reduce-scatter shot); rank-1 all-to-alls are
        scale/column movement and never do — both dialects."""
        stablehlo = (
            '%0 = "stablehlo.all_to_all"(%a) <{split_count = 8 : i64}> :'
            " (tensor<8x301xi8>) -> tensor<8x301xi8>\n"
            '%1 = "stablehlo.all_to_all"(%s) <{split_count = 8 : i64}> :'
            " (tensor<8xf32>) -> tensor<8xf32>\n"
        )
        ops = hlo_audit.payload_alltoalls(stablehlo)
        assert [(o.kind, o.dtype, o.rank) for o in ops] == [
            ("all-to-all", "i8", 2),
        ]
        hlo = (
            "ENTRY %main {\n"
            "  %aa = f32[8,16]{1,0} all-to-all(f32[8,16]{1,0} %x), "
            "channel_id=1\n"
            "  %sc = f32[8]{0} all-to-all(f32[8]{0} %s), channel_id=2\n"
            "}\n"
        )
        ops2 = hlo_audit.payload_alltoalls(hlo)
        assert [(o.kind, o.rank) for o in ops2] == [("all-to-all", 2)]

    def test_alltoalls_count_violation_names_exclusions(self):
        text = (
            '%0 = "stablehlo.all_to_all"(%a) <{split_count = 8 : i64}> :'
            " (tensor<8x301xi8>) -> tensor<8x301xi8>\n"
            '%1 = "stablehlo.all_to_all"(%s) <{split_count = 8 : i64}> :'
            " (tensor<8xf32>) -> tensor<8xf32>\n"
        )
        violations = hlo_audit.audit(
            text, hlo_audit.ProgramExpectation.parse("alltoalls=2")
        )
        assert violations
        assert "found 1" in violations[0]
        assert "rank-1 scale/column" in violations[0]
        hlo_audit.assert_program(text, "alltoalls=1")  # the true count

    def test_op_bytes_by_kind_in_expectation_diffs(self):
        """A failed count carries the per-kind payload-byte totals —
        where the wire bytes actually went is the first question."""
        with pytest.raises(hlo_audit.ProgramAuditError) as e:
            hlo_audit.assert_program(HLO_SAMPLE, "one-reduction")
        msg = str(e.value)
        assert "payload op_bytes by kind:" in msg
        assert f"all-reduce={2410 * 4}" in msg
        assert f"all-gather={8 * 2410}" in msg
        totals = hlo_audit.op_bytes_by_kind(HLO_SAMPLE)
        # The scalar all-reduce and the rank-1 scale gather contribute 0.
        assert totals == {
            "all-reduce": 2410 * 4, "all-gather": 8 * 2410,
        }

    def test_op_bytes(self):
        op = hlo_audit.CollectiveOp(
            kind="all-to-all", dtype="i8", shape=(8, 301), line=1, index=0
        )
        assert hlo_audit.op_bytes(op) == 8 * 301
        op32 = hlo_audit.CollectiveOp(
            kind="all-reduce", dtype="f32", shape=(2410,), line=1, index=0
        )
        assert hlo_audit.op_bytes(op32) == 2410 * 4

    def test_assert_program_structured_diff(self):
        """The failure message is a structured diff — expected counts,
        every observed op with dtype/shape/line — not a regex mismatch."""
        with pytest.raises(hlo_audit.ProgramAuditError) as e:
            hlo_audit.assert_program(
                HLO_SAMPLE, "one-reduction,wire=int8"
            )
        msg = str(e.value)
        assert "expected exactly 1 gradient reduction(s)" in msg
        assert "found 2" in msg
        assert "all-reduce f32[2410]" in msg
        assert "off-wire traffic" in msg

    def test_wire_on_empty_program_is_a_violation(self):
        with pytest.raises(hlo_audit.ProgramAuditError,
                           match="NO gradient reductions"):
            hlo_audit.assert_program("HloModule empty", "wire=bf16")

    def test_clean_expectations_pass(self):
        hlo_audit.assert_program(HLO_SAMPLE, "reductions=2,donates=2")
        assert hlo_audit.audit(
            "HloModule empty", hlo_audit.ProgramExpectation.parse(
                "no-collectives"
            )
        ) == []


class TestRealPrograms:
    """Integration over real lowered steps via the shared probe."""

    def test_int8_step_audits_one_i8_payload_gather(self):
        import horovod_tpu as hvt
        from horovod_tpu.analysis import step_probe

        hvt.init()
        x, y = step_probe.probe_data()
        text = step_probe.lowered_step_text(
            step_probe.build_trainer(2, "int8"), x, y, 2
        )
        hlo_audit.assert_program(text, "one-reduction,wire=int8")
        grads = hlo_audit.gradient_reductions(text)
        assert [(o.kind, o.dtype) for o in grads] == [("all-gather", "i8")]
        # The two-shot wire (PR 10): one i8 all-to-all (the reduce-
        # scatter shot) + the counted i8 chunk gather, with TWO rank-1
        # f32 scale gathers (one per shot) in the program but not in
        # the count.
        ops = hlo_audit.collective_ops(text)
        assert [
            (o.kind, o.dtype) for o in ops if o.kind == "all-to-all"
        ] == [("all-to-all", "i8")]
        scale_gathers = [
            o for o in ops if o.kind == "all-gather" and o.rank == 1
        ]
        assert len(scale_gathers) == 2
        assert all(o.dtype == "f32" for o in scale_gathers)

    def test_compiled_step_donation_extracted(self):
        """The donated TrainState surfaces as input_output_alias entries
        in the compiled HLO — `donates=1` is auditable."""
        import horovod_tpu as hvt
        from horovod_tpu.analysis import step_probe

        hvt.init()
        x, y = step_probe.probe_data()
        tr = step_probe.build_trainer(1, "none", error_feedback=False)
        # Reuse the probe plumbing up to lowering, then compile.
        import jax.numpy as jnp

        from horovod_tpu.parallel import sharding as sharding_lib

        state = tr.build(x[: tr.dp_size])
        batch = tr._shard((x[:32], y[:32]))
        acc = sharding_lib.replicate(tr.zero_metrics(), tr.mesh)
        ctext = tr._train_step.lower(
            state, batch, jnp.asarray(1.0, jnp.float32), acc
        ).compile().as_text()
        assert len(hlo_audit.donated_args(ctext)) >= 1
        hlo_audit.assert_program(ctext, "donates=1")


def _run_audit(args, env=None):
    full_env = dict(os.environ)
    if env:
        full_env.update(env)
    return subprocess.run(
        [sys.executable, "-m", "horovod_tpu.analysis.audit_cli"] + args,
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env=full_env,
    )


class TestAuditCLI:
    """Exit-code contract + the canonical K=4 + int8 tier-1 gate."""

    def test_canonical_k4_int8_step_gate(self):
        """THE CI gate (ISSUE 9): the canonical accumulating int8 step
        carries exactly one i8 payload reduction AND the overlap peel —
        asserted end to end through the real CLI against a freshly
        lowered program."""
        proc = _run_audit([
            "step", "--platform", "cpu", "--k", "4",
            "--compression", "int8",
            "--expect", "one-reduction,wire=int8,overlap",
        ])
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "ok" in proc.stdout and "overlap peel verified" in proc.stdout

    def test_canonical_k4_zero1_int8_step_gate(self):
        """THE composed-path CI gate (ISSUE 10): K=4 + shard_update +
        int8 compiles to exactly ONE bucketed scatter-form reduction per
        optimizer step (no full-payload all-reduce), wire dtype i8 on
        the lowered StableHLO, and the overlap peel still holds —
        end to end through the real CLI."""
        proc = _run_audit([
            "step", "--platform", "cpu", "--k", "4", "--zero1",
            "--compression", "int8",
            "--expect", "scatters=1,wire=int8,overlap",
        ])
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "ok" in proc.stdout and "overlap peel verified" in proc.stdout

    def test_canonical_k4_zero1_overlap_gate(self):
        """THE ISSUE 12 acceptance gate: the UNCOMPRESSED composed step
        (K=4 + shard_update) packs every leaf — tail family included —
        into ONE leaf-aligned scatter bucket, and the overlap peel
        holds with the scatter count UNCHANGED between the peeled and
        serialized programs (the peel re-schedules the buckets, it must
        not re-bucket the reduction)."""
        proc = _run_audit([
            "step", "--platform", "cpu", "--k", "4", "--zero1",
            "--expect", "scatters=1,overlap",
        ])
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "ok" in proc.stdout and "overlap peel verified" in proc.stdout

    def test_quantized_ici_two_hop_audits_shape(self):
        """--dcn fakes the two-hop factoring and --compression-ici int8
        puts the quantized wire on its ICI hop: the derived expectation
        degrades to the shape-only scatter-reduction (the hop-1 payload
        all-to-all rides next to the hop-2 reduce-scatter, so exact
        counts depend on the factoring) and the program passes it."""
        proc = _run_audit([
            "step", "--platform", "cpu", "--k", "4", "--zero1",
            "--dcn", "2", "--compression-ici", "int8",
        ])
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "derived --expect scatter-reduction" in proc.stdout

    def test_zero1_gate_derives_scatter_expectation(self):
        """`--zero1` without --expect derives the scatter-form
        expectation (scatters=1 for the quantized dense layout)."""
        proc = _run_audit([
            "step", "--platform", "cpu", "--k", "4", "--zero1",
            "--compression", "int8",
        ])
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "derived --expect scatters=1,wire=int8" in proc.stdout

    def test_moe_dispatch_combine_gate(self):
        """THE EP wire gate (ISSUE 14 satellite of ROADMAP item 4): the
        MoE probe's dispatch/combine lowers to exactly TWO payload
        all-to-alls through `collectives.all_to_all` — asserted end to
        end through the real CLI against a freshly lowered program."""
        proc = _run_audit(["moe", "--platform", "cpu"])
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "derived --expect alltoalls=2" in proc.stdout
        assert "2 payload all-to-all(s)" in proc.stdout

    def test_moe_gate_wrong_count_fails(self):
        proc = _run_audit([
            "moe", "--platform", "cpu", "--expect", "alltoalls=3",
        ])
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "payload all-to-all" in proc.stdout

    def test_overlap_knob_off_fails_gate(self):
        """HVT_OVERLAP_REDUCTION=0 must fail the overlap expectation —
        the structural gate catches a fleet de-overlapped by env."""
        proc = _run_audit([
            "step", "--platform", "cpu", "--k", "4",
            "--compression", "int8",
            "--expect", "one-reduction,wire=int8,overlap",
        ], env={"HVT_OVERLAP_REDUCTION": "0"})
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "overlap" in proc.stdout

    def test_wire_violation_fails(self):
        """An uncompressed step audited against wire=int8 exits 1 with
        the off-wire op in the diff (the compression invariant)."""
        proc = _run_audit([
            "step", "--platform", "cpu", "--k", "2",
            "--compression", "none", "--expect", "wire=int8",
        ])
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "off-wire" in proc.stdout

    def test_usage_error_exits_2(self):
        proc = _run_audit(["step", "--expect", "bogus=1"])
        assert proc.returncode == 2
        assert "unknown expectation" in proc.stderr

    def test_file_subcommand(self, tmp_path):
        p = tmp_path / "step.hlo"
        p.write_text(HLO_SAMPLE)
        ok = _run_audit(["file", str(p), "--expect", "reductions=2"])
        assert ok.returncode == 0, ok.stdout + ok.stderr
        bad = _run_audit(["file", str(p), "--expect", "one-reduction"])
        assert bad.returncode == 1
        assert "found 2" in bad.stdout
        missing = _run_audit(
            ["file", str(tmp_path / "nope.hlo"), "--expect", "reductions=1"]
        )
        assert missing.returncode == 2
