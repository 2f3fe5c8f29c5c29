"""The paper's metric: share of the paged-decode program's device time in ops
whose innermost ``ng:`` group (from the compiled HLO's op_name) is not gemm."""

from chipbench.trace import DECODE


def read(ctx):
    groups = ctx.trace.group_time(DECODE)
    total = sum(groups.values())
    return 100.0 * (total - groups.get("gemm", 0.0)) / total if total else None
