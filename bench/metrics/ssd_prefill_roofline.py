"""Share of its roofline the SSD chunk-scan kernel reaches in prefill.

Needed work, per Mamba layer, for every prompt admitted in the window at
its real length: the SSD algorithm at the published widths and chunk size
(``mamba_chunk_size``): per chunk of q positions, C B^T over its causal
pairs (shared by the heads, n_groups 1), and per head the weighted sum over
those pairs, the carried state's contribution and the state update; read
x, B and C (bf16), dt (f32), the initial state (f32), write y (bf16) and
the final state (f32). Padding of the wave past a prompt's length is not
needed work. Kernel time is the summed device time of the Pallas kernels
named ``ssd_scan`` (the ``pallas_call`` name XLA gives the instruction) in
the prefill program ``_pf``. Memory-bound at the widths served: a chunk's
per-head state update reuses each loaded byte a few times only."""
from bench import trace as tr

BF16, F32 = 2, 4
PROGRAM = r"_pf\b"
KERNEL = r"ssd_scan"


def needed(m: dict, n: int):
    """(FLOP, bytes) of one n-token prompt's SSD scan in one layer."""
    h, p, s = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    q_max = m["mamba_chunk_size"]
    flops = 0
    for start in range(0, n, q_max):
        q = min(q_max, n - start)
        pairs = q * (q + 1) // 2
        flops += 2 * pairs * s + h * (2 * pairs * p + 4 * q * s * p)
    bytes_ = (2 * n * h * p * BF16          # x in, y out
              + 2 * n * s * BF16            # B, C
              + n * h * F32                 # dt
              + 2 * h * p * s * F32)        # state in and out
    return flops, bytes_


def read(ctx):
    if ctx.trace is None:
        return None
    t = tr.matching_s(ctx.trace, KERNEL, module=PROGRAM, kernel=True)
    prompts = ctx.window.admitted_prompts()
    if not t or not prompts:
        return None
    layers = sum(k == "mamba" for k in ctx.cfg["layer_types"])
    f = sum(needed(ctx.cfg, n)[0] for n in prompts) * layers
    b = sum(needed(ctx.cfg, n)[1] for n in prompts) * layers
    return ctx.roofline("ssd_prefill_roofline", f, b, t)
