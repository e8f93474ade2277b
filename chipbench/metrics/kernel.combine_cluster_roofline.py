"""Share of the HBM roofline that the cluster-fused combine kernel
reaches: the bytes its calls must move (`kernel_cost`, from the `G`, `Q`,
`L`, `W`, `S` of each `combine.call` span of the `combine_cluster`
kernel) over the device time of its custom calls in the trace
(`combine_cluster_pallas`), as a share of the chip's HBM bandwidth."""

import jax

from chipbench import kernel_cost, program

KERNEL = "combine_cluster_pallas"


def read(rec):
    if rec.trace is None:
        return None
    calls = [s for s in program.named(rec, "combine.call")
             if s.attrs.get("kernel") == "combine_cluster"]
    device_s = sum(e.dur_ns for e in rec.trace.device_events
                   if e.is_kernel and e.op.startswith(KERNEL)) * 1e-9
    peak = kernel_cost.hbm_bytes_per_s(jax.devices()[0].device_kind)
    if not calls or device_s <= 0 or peak is None:
        return None
    moved = sum(kernel_cost.combine_cluster_bytes(
        *(s.attrs[k] for k in "GQLWS")) for s in calls)
    return 100.0 * moved / device_s / peak
