"""llm_mfu.score: the model step's share of the chip's bf16 peak.

Tokens whose forward pass completed in the traced window (the rows of the
completed requests times the window length ``seq_len``) times the forward
operations of one token (``costs_llm.token_flops``, from the
configuration's shapes, causal attention at its mean context), over the
window's seconds times the chips times the bf16 peak (``peaks.py``)."""

from cellbench.costs_llm import token_flops
from cellbench.peaks import peak


def read(run):
    if run.trace is None or not run.ok:
        return None
    s = run.cell.mix["request"]["seq_len"]
    tokens = sum(r["units"] for r in run.ok) * s
    flops = tokens * token_flops(run.cell.config, s)
    return 100.0 * flops / (run.trace["window_s"] * run.trace["chips"]
                            * peak(run.device_kind)["bf16_flops"])
