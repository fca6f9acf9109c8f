"""Facade and router: mean wall seconds of the executor's ``batch``
stages that end in the window.  The pool runs a routed batch to its
longest output inside one pump, so an arrival during it can be neither
submitted nor admitted until it ends.  A batch the close cuts never
ends in the window and is left out."""


def read(rec):
    held = [t1 - t0 for name, t0, t1, *_ in rec.stages
            if name == "batch" and rec.t0 <= t1 <= rec.close]
    return sum(held) / len(held) if held else None
