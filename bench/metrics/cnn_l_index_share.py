"""Share of the device's busy time in CNN-L's second NAM level, in %: the
device operations of the program's ``cnn_l.index`` scope (``tanh``, the
index tree's descent, the logit table read and the sum over packets) over
the traced slice's busy time, both summed over the cell's chips. ``None``
where the trace has none of them.

On the TPU the trace names each device operation by its HLO instruction
text (``%fusion.30 = s32[...] fusion(... %bitcast.81), ...``) and carries
no name stack, so the scope is found by the names the compiler gives its
operations: every fusion of the CNN-L forward but the kernel's input pad
(``%pad_convert_fusion``) belongs to the index stage, and so does every
operation that reads one. Its other unfused operations (scalar index
arithmetic, two iotas) are left out. ``tests/test_tpu_compile.py`` checks
these names against the scope in the forward compiled for a v5e."""

KERNEL = "cnn_l_index"
PATTERNS = ("%fusion", "%compare_select_fusion", "%select_select_fusion",
            "%compare_reduce_fusion", "%bitcast_reduce_fusion",
            "%broadcast_clamp_fusion", "%pad_bitcast_fusion",
            "%broadcast_add_fusion")


def read(ctx):
    if ctx.trace is None or not ctx.trace["busy_s"]:
        return None
    t = ctx.trace["kernel_s"].get(KERNEL, 0.0)
    if not t:
        return None
    return 100.0 * t / (ctx.trace["busy_s"] * ctx.chips)
