"""The latent layers' attention kernels against their roofline: the least
time over the (query, key) pairs the step counted on those layers
(``attn_keys_per_query.latent_attention``) x every head x (q.k 192 + p.v
128), forward once and backward at twice (``flops_linear.latent_floor_s``),
over the device time of the ops under ``decoder/attn_latent/attend`` per step
(the forward runs again where a layer is recomputed; the floor counts it
once)."""
from perfbench import flops_linear, lm_trace
from perfbench.metrics import latent_attention_device_ms_per_step

KIND = "latent_attention"


def read(run):
    ms = latent_attention_device_ms_per_step.read(run)
    seen = lm_trace.counters(run)
    if not ms or seen is None or KIND not in seen["keys_per_query"]:
        return None
    floor = flops_linear.latent_floor_s(run.cell.config, run.cell.traffic,
                                        seen["keys_per_query"][KIND], run.peaks)
    return 100.0 * floor / (ms / 1e3)
