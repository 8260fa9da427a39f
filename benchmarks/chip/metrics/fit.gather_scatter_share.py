"""fit.gather_scatter_share: self time of the fit step's data path over the
device's busy time in the window, from the trace.

The data path is every op of the window but the fused step kernel
(``nomad_step``, found as ``nomad_step_roofline`` finds it) and control
flow (``while``, ``conditional``, ``call``: loop bookkeeping). On the
program as it stands that is the θ gathers and the scatter-add with the
sorts XLA runs for it (most of it), the heads' sampling and cell search,
and the means refresh once per dispatch. It is counted as what is not the
kernel so that it stays whole when a change replaces the gathers or the
scatter with other ops: a sort and segment sum, a Pallas scatter, or any
op of another name or operand count here, and the share falls only when
the data path as a whole takes less of the step. Where the kernel is not
found, its time counts here too and ``nomad_step_roofline`` is silent."""

from lib.trace import CONTROL, is_kernel, kind


def read(ctx):
    red = ctx["trace"]
    blocks = ctx["nomad_step_blocks"]
    other = red.self_time(lambda text: kind(text) in CONTROL or is_kernel(text, blocks))
    return 100.0 * (red.busy_s - other) / red.busy_s
