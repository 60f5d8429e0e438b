"""Matmul FLOPs and operation kinds from optimised HLO text."""
from harness import hlo

TEXT = """
HloModule jit_step

%fused_computation (p0: f32[4096,1600], p1: f32[1600,6400]) -> f32[4096,6400] {
  %p0 = f32[4096,1600]{1,0} parameter(0)
  %p1 = f32[1600,6400]{1,0} parameter(1)
  %convolution.1 = f32[4096,6400]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf
  ROOT %tanh.0 = f32[4096,6400]{1,0} tanh(%convolution.1)
}

%body (arg: (s32[], f32[4096,1600])) -> (s32[], f32[4096,1600]) {
  %arg = (s32[], f32[4096,1600]) parameter(0)
  %x = f32[4096,1600]{1,0} get-tuple-element(%arg), index=1
  %w = f32[1600,6400]{1,0} constant({...})
  %convolution_tanh_fusion = f32[4096,6400]{1,0} fusion(%x, %w), kind=kOutput, calls=%fused_computation
  %w2 = f32[6400,1600]{1,0} constant({...})
  %dot.3 = f32[4096,1600]{1,0} dot(%convolution_tanh_fusion, %w2), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %i = s32[] get-tuple-element(%arg), index=0
  ROOT %t = (s32[], f32[4096,1600]) tuple(%i, %dot.3)
}

%cond (arg: (s32[], f32[4096,1600])) -> pred[] {
  %arg = (s32[], f32[4096,1600]) parameter(0)
  ROOT %c = pred[] constant(true)
}

ENTRY %main (a: f32[4096,1600], m: f32[4096,1600]) -> f32[4096,1600] {
  %a = f32[4096,1600]{1,0} parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[4096,1600]) tuple(%z, %a)
  %while.5 = (s32[], f32[4096,1600]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"12"}}
  %out = f32[4096,1600]{1,0} get-tuple-element(%while.5), index=1
  %m = f32[4096,1600]{1,0:T(8,128)S(1)} parameter(1)
  %delta_quantize_pack.1 = (u8[4096,800]{1,0:T(8,128)(4,1)}, f32[4096,1]{1,0:T(8,128)}, f32[4096,1600]{1,0:T(8,128)S(1)}) custom-call(%out, %m), custom_call_target="tpu_custom_call"
  %cp = f32[4096,1600]{1,0} collective-permute(%out), source_target_pairs={{0,1}}
  ROOT %r = f32[4096,1600]{1,0} add(%out, %cp)
}
"""


def test_op_table_kinds_flops_and_trip_counts():
    t = hlo.op_table(TEXT)
    mm = 2.0 * 4096 * 6400 * 1600
    assert t["convolution_tanh_fusion"] == {"kind": "matmul", "flops": mm,
                                            "count": 12}
    assert t["dot.3"] == {"kind": "matmul", "flops": mm, "count": 12}
    assert t["delta_quantize_pack.1"]["kind"] == "kernel"
    # HBM: the activation in, the codes and the scales out; the message
    # in and out lies in the core's memory (S(1))
    assert t["delta_quantize_pack.1"]["hbm_bytes"] == \
        4096 * 1600 * 4 + 4096 * 800 + 4096 * 4
    assert t["cp"]["kind"] == "collective"
    assert t["r"]["kind"] == "other"
    assert t["while.5"] == {"kind": "control", "flops": 0.0, "count": 1}


CONVS = """
HloModule jit_step

ENTRY %main (a: f32[4,25,1024,512], b: bf16[4,25,512,64], x: f32[4,1024,1600,1], w: bf16[25,64,1600,1]) -> f32[4,25,1024,64] {
  %a = f32[4,25,1024,512]{3,2,1,0} parameter(0)
  %b = bf16[4,25,512,64]{3,2,1,0} parameter(1)
  %x = f32[4,1024,1600,1]{3,2,1,0} parameter(2)
  %w = bf16[25,64,1600,1]{3,2,1,0} parameter(3)
  %batched = f32[4,25,1024,64]{2,3,1,0} convolution(%a, %b), window={size=4x25 stride=3x24 lhs_dilate=4x25}, dim_labels=01bf_01io->01bf
  %heads = f32[4,1024,25,64]{1,3,2,0} convolution(%x, %w), window={size=1x25 pad=0_0x24_24 rhs_reversal=0x1}, dim_labels=0bf1_1oi0->0b1f
  ROOT %r = f32[4,25,1024,64]{2,3,1,0} add(%batched, %batched)
}
"""


def test_convolutions_that_write_batched_matmuls():
    """Batch dimensions written as dilated windows, and heads as a
    window over a padded input of size 1, meet one tap each."""
    t = hlo.op_table(CONVS)
    assert t["batched"]["flops"] == 2.0 * 4 * 25 * 1024 * 64 * 512
    assert t["heads"]["flops"] == 2.0 * 4 * 1024 * 25 * 64 * 1600


def test_kernel_names_as_the_compiler_gives_them():
    from kernels.quant_pack import kernel_of
    assert kernel_of("jvp_jit_delta_quantize_pack__.3") == \
        "delta_quantize_pack"
    assert kernel_of("transpose_jvp_jit_quantize_pack___.4") == \
        "quantize_pack"
    assert kernel_of("transpose_jvp_jit_unpack_dequant___.5") == \
        "unpack_dequant"
    assert kernel_of("custom-call.7") == "custom-call"
