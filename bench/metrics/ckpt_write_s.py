"""Seconds a checkpoint write takes on the checkpoint writer's thread:
the mean ``ckpt.write`` span of the program (``repro.obs``).  In a cell
that checkpoints only at step 0 this is the write set-up waits for before
the window opens.  None where the program records no such span."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    s = obs.table()["spans"].get("ckpt.write")
    return None if not s else s["total_s"] / s["count"]
