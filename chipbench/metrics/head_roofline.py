"""The fused LM-head kernel's share of its roofline: per call the larger of
required operations over the peak rate and required bytes (the head weight
once, the hidden rows in, the results out) over the peak bandwidth, summed,
over the kernel's summed device time (percent).  Layer: kernels."""
import counts


def read(run):
    m, pk, ref = run.model, run.peaks, run.reference
    block = run.config["serving"]["block_length"]
    per_tick = counts.traced_ticks(run)
    itemsize = 4 if m["dtype"] == "float32" else 2
    share = []
    for d in run.trace["devices"].values():
        if not d["head_calls"] or not per_tick:
            continue
        least = [max(counts.head_flops(m, len(a) * block, ref)
                     / pk["bf16_flops_s"],
                     counts.head_bytes(m, len(a) * block, itemsize, ref)
                     / pk["hbm_bytes_s"]) for a in per_tick]
        share.append(d["head_calls"] * sum(least) / len(least) / d["head_s"])
    return 100.0 * sum(share) / len(share) if share else None
