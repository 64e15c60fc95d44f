"""Host milliseconds per Linear Road read (balance, expenditure or
notice): the program's ``gateway.lr_read`` spans."""
import numpy as np

PROGRAM = True


def read(ctx):
    spans = ctx.program.get("spans")
    if spans is None:
        return None
    hit = spans["name"] == "gateway.lr_read"
    return 1e3 * float(np.mean((spans["t1"] - spans["t0"])[hit])) if hit.any() else None
