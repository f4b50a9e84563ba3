"""obs/hlo_audit.py on hand-written HLO text in the form XLA:TPU prints:
what counts as a weight moved before it is read, and what does not."""

from megatron_llm_tpu.obs.hlo_audit import relayout_bytes

# a layer scan of 4 trips (the TPU form: no known_trip_count, the bound is
# the condition's constant) whose body slices one layer's [1,2048,2048]
# bf16 weight out of the stack in a fusion of its own and re-lays it, a
# second weight sliced inside its matmul's fusion, a small copy, an
# asynchronous prefetch; in the entry a copy of a [16384,1024] table
_HLO = """\
HloModule jit__decode_impl, is_scheduled=true

%fused_slice (p0: bf16[4,2048,2048], p1: s32[]) -> bf16[1,2048,2048] {
  %p0 = bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)} parameter(0)
  %p1 = s32[]{:T(128)} parameter(1)
  %zero = s32[]{:T(128)} constant(0)
  ROOT %dynamic_slice.1 = bf16[1,2048,2048]{2,1,0:T(8,128)(2,1)S(1)} dynamic-slice(%p0, %p1, %zero, %zero), dynamic_slice_sizes={1,2048,2048}
}

%fused_matmul (q0: bf16[4,2048,2048], q1: s32[], q2: bf16[16,2048]) -> bf16[16,2048] {
  %q0 = bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)} parameter(0)
  %q1 = s32[]{:T(128)} parameter(1)
  %q2 = bf16[16,2048]{1,0:T(8,128)(2,1)} parameter(2)
  %zero.1 = s32[]{:T(128)} constant(0)
  %dynamic_slice.2 = bf16[1,2048,2048]{2,1,0:T(8,128)(2,1)} dynamic-slice(%q0, %q1, %zero.1, %zero.1), dynamic_slice_sizes={1,2048,2048}
  %bitcast.1 = bf16[2048,2048]{1,0:T(8,128)(2,1)} bitcast(%dynamic_slice.2)
  ROOT %convolution.1 = bf16[16,2048]{1,0:T(8,128)(2,1)} convolution(%q2, %bitcast.1), dim_labels=bf_io->bf
}

%body (arg: (s32[], bf16[16,2048], bf16[4,2048,2048], bf16[4,2048,2048])) -> (s32[], bf16[16,2048], bf16[4,2048,2048], bf16[4,2048,2048]) {
  %arg = (s32[]{:T(128)}, bf16[16,2048]{1,0:T(8,128)(2,1)}, bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)}, bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%arg), index=0
  %x = bf16[16,2048]{1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %wa = bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=2
  %wb = bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=3
  %constant_dynamic-slice_fusion.2 = bf16[1,2048,2048]{2,1,0:T(8,128)(2,1)S(1)} fusion(%wa, %i), kind=kLoop, calls=%fused_slice
  %copy.217 = bf16[1,2048,2048]{1,2,0:T(8,128)(2,1)S(1)} copy(%constant_dynamic-slice_fusion.2)
  %copy.3 = bf16[16,2048]{0,1:T(8,128)(2,1)} copy(%x)
  %copy-start.1 = (bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)S(1)}, bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%wb)
  %copy-done.1 = bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.1)
  %fusion.292 = bf16[16,2048]{1,0:T(8,128)(2,1)} fusion(%wb, %i, %x), kind=kOutput, calls=%fused_matmul
  %one = s32[]{:T(128)} constant(1)
  %next = s32[]{:T(128)} add(%i, %one)
  ROOT %tuple.1 = (s32[]{:T(128)}, bf16[16,2048]{1,0:T(8,128)(2,1)}, bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)}, bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)}) tuple(%next, %fusion.292, %wa, %wb)
}

%cond (carg: (s32[], bf16[16,2048], bf16[4,2048,2048], bf16[4,2048,2048])) -> pred[] {
  %carg = (s32[]{:T(128)}, bf16[16,2048]{1,0:T(8,128)(2,1)}, bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)}, bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %ci = s32[]{:T(128)} get-tuple-element(%carg), index=0
  %bound = s32[]{:T(128)} constant(4)
  ROOT %lt = pred[]{:T(512)} compare(%ci, %bound), direction=LT
}

ENTRY %main (word: bf16[16384,1024], x0: bf16[16,2048], wa0: bf16[4,2048,2048], wb0: bf16[4,2048,2048]) -> bf16[16,2048] {
  %word = bf16[16384,1024]{0,1:T(8,128)(2,1)} parameter(0)
  %x0 = bf16[16,2048]{1,0:T(8,128)(2,1)} parameter(1)
  %wa0 = bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)} parameter(2)
  %wb0 = bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)} parameter(3)
  %copy.154 = bf16[16384,1024]{1,0:T(8,128)(2,1)} copy(%word)
  %izero = s32[]{:T(128)} constant(0)
  %init = (s32[]{:T(128)}, bf16[16,2048]{1,0:T(8,128)(2,1)}, bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)}, bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)}) tuple(%izero, %x0, %wa0, %wb0)
  %while.1 = (s32[]{:T(128)}, bf16[16,2048]{1,0:T(8,128)(2,1)}, bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)}, bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)}) while(%init), condition=%cond, body=%body
  ROOT %out = bf16[16,2048]{1,0:T(8,128)(2,1)} get-tuple-element(%while.1), index=1
}
"""

_LAYER = 2048 * 2048 * 2
_TABLE = 16384 * 1024 * 2


def test_counts_weight_sized_moves_a_trip_and_nothing_else():
    """The table's copy once; in the loop the stand-alone slice fusion
    and the copy of its result, four trips each.  Not the slice inside
    the matmul's fusion, the [16,2048] copy under the threshold, the
    copy-start/copy-done prefetch of a whole stack."""
    assert relayout_bytes(_HLO) == {
        "bf16[16384,1024]": _TABLE,
        "bf16[1,2048,2048]": 4 * 2 * _LAYER,
    }


def test_threshold_is_the_callers():
    assert relayout_bytes(_HLO, min_bytes=_TABLE + 1) == {}
    small = relayout_bytes(_HLO, min_bytes=16 * 2048 * 2)
    assert small["bf16[16,2048]"] == 4 * 16 * 2048 * 2
    assert small["bf16[1,2048,2048]"] == 4 * 2 * _LAYER


def test_a_program_that_moves_nothing_reads_empty():
    clean = "\n".join(
        line for line in _HLO.splitlines()
        if "copy.154" not in line and "copy.217" not in line
        and "constant_dynamic-slice_fusion.2" not in line)
    assert relayout_bytes(clean) == {}
