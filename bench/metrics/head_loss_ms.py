"""Device time per window step of the output head and the loss, in ms:
the operations under named scope ``loss`` (the output head's matmuls and
the cross-entropy, forward and backward) in both sides' training steps,
``jit_ref_step`` and ``jit_cand_step``, averaged over the chips that run
each.  None where the trace names no such operation in either step."""

PROGRAMS = ("jit_ref_step", "jit_cand_step")


def read(ctx):
    got = [ctx["trace"].scope_s(p, "loss") for p in PROGRAMS]
    if any(s is None for s in got):
        return None
    return 1e3 * sum(got) / ctx["steps"]
