"""moe_roofline.score: the routed experts' grouped matmuls against their
roofline.

The least time the traced window's routed-expert work could take, over the
device time of the ops named ``moe_gmm*`` (the program's grouped-matmul
kernel). Per dispatched block and expert layer the work is
``costs_llm.routed_flops`` a token at the bf16 peak, or
``costs_llm.routed_bytes`` at the HBM bandwidth, whichever is longer.
Nothing is returned when the trace holds no such op.
"""

from cellbench.costs_llm import routed_bytes, routed_flops
from cellbench.peaks import peak

KERNEL = "moe_gmm"


def read(run):
    if run.trace is None or not run.ok:
        return None
    kernel_s = sum(s for name, s in run.trace["ops"].items()
                   if name.startswith(KERNEL)) / run.trace["chips"]
    if kernel_s <= 0:
        return None
    cfg, req = run.cell.config, run.cell.mix["request"]
    p = peak(run.device_kind)
    tokens = req["block"] * req["seq_len"]
    per_block = max(tokens * routed_flops(cfg) / p["bf16_flops"],
                    routed_bytes(cfg, tokens) / p["hbm_bytes_per_s"])
    blocks = sum(r["units"] for r in run.ok) / req["block"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return 100.0 * blocks * layers * per_block / kernel_s
