"""One prom_ratio less another: a stage of the program's `review` path
net of the collector (stage seconds less the stage's collector seconds,
both over the reviews booked), or the door's wait for a replica less
the replica's own service time.

args: a and b, each a prom_ratio args object (its own surface, num, den
and scale).  b is read with zero_ok, so a stage that met no collection
subtracts 0.  None when a reads None, and None when b does: where b's
denominator did not grow (a program without the `review` path) the
difference would only be a under another name."""

from readers import prom_ratio


def read(raw: dict, args: dict):
    a = prom_ratio.read(raw, args["a"])
    if a is None:
        return None
    b = prom_ratio.read(raw, dict(args["b"], zero_ok=True))
    if b is None:
        return None
    return a - b
