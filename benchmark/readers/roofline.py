"""A kernel's share of its roofline: the least time one chip could take
for a step's operations and bytes at the published peaks, over the device
time a step took. f32 matmuls at default precision run as bf16 passes on
the MXU, so the bf16 peak is the compute roof."""

from benchmark import ops


def read(record, params):
    device_step_s = record["trace"].get("device_step_s")
    peaks = record["peaks"]
    if not device_step_s or not peaks:
        return None
    chips = record["chips"]
    least, bound = ops.roofline_seconds(
        record["driver"]["ops_per_step"] / chips,
        record["driver"]["bytes_per_step"] / chips,
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    print(f"[roofline] bound={bound} least_s={least:.6f} "
          f"device_step_s={device_step_s:.6f}", flush=True)
    return 100.0 * least / device_step_s
