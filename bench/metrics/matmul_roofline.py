"""Share of the roofline reached by the local block products.

The work is counted from shapes (each chip's q products of its blocks,
``matmul_flops_per_chip``), whoever computes it. The time is that of the
leaf ops that compute it on each chip: XLA's dot fusions (output fusions,
with the accumulate fused beside the dot, named ``convolution...``) and
Pallas kernels (``tpu_custom_call``), averaged over chips. The operands'
conversion to bf16, which XLA hoists before the shifts on a grid, is not
counted. The bound is bf16 FLOP/s: the configuration's
product rounds its operands to bf16.
"""
import re

MATMUL_OP = re.compile(
    r'kind=kOutput|\bconvolution\(|\bdot\(|custom_call_target="tpu_custom_call"')


def read(ctx):
    t_ns = ctx.summary.op_ns(lambda text: MATMUL_OP.search(text) is not None)
    if t_ns == 0:
        return None
    flops = ctx.work["matmul_flops_per_chip"] * ctx.steps
    return 100.0 * flops / ctx.peaks["bf16_flops_per_s"] / (t_ns * 1e-9)
