"""Executables built or loaded inside the window: the growth of
/debug/compilez' provenance_mix (every path, every provenance) between
the window's opening and its close."""

from readers.value import lookup


def read(raw: dict, args: dict):
    before = lookup(raw, args.get("before", "window.compilez_before"))
    after = lookup(raw, args.get("after", "window.compilez_after"))
    if before is None or after is None:
        return None
    total = lambda cz: sum((cz.get("provenance_mix") or {}).values())
    return float(total(after) - total(before))
