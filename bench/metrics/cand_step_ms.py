"""Device time per step of the candidate's jitted training step
(``parallel.api.make_candidate_train_step``), in ms, averaged over the
chips that run it."""


def read(ctx):
    s = ctx["programs"].get("cand_step")
    return None if s is None else 1e3 * s / ctx["steps"]
