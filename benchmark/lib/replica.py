#!/usr/bin/env python3
"""The replica under test, `python -m gatekeeper_tpu.fleet.replica`'s
own main(), with one thing beside it that only the chip-holding process
can do: a control port that starts and stops jax's profiler, reduces
the trace (lib/trace.py) and reads the device's peak memory.

    python3 benchmark/lib/replica.py <platform> <replica args...>

Prints {"event": "control", "port": N} before the replica's ready line.
Control commands, one line each, one JSON line back:
  trace_start <dir> | trace_stop | memstats | gc_full
gc_full answers with the interpreter's full (generation 2) collections
so far: [[time.monotonic() at start, seconds], ...] (gc.callbacks).
"""

import json
import os
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def control(lsock, pauses):
    import jax

    from lib import chip, trace

    state = {}

    def handle(cmd):
        if cmd[0] == "gc_full":
            return {"ok": True, "pauses": list(pauses.events)}
        if cmd[0] == "trace_start":
            state.update(dir=cmd[1], t0=time.monotonic())
            jax.profiler.start_trace(
                cmd[1], profiler_options=trace.start_options())
            return {"ok": True}
        if cmd[0] == "trace_stop":
            # the traced window ends here; writing the trace takes seconds
            window_s = time.monotonic() - state["t0"]
            jax.profiler.stop_trace()
            return {"ok": True,
                    "trace": trace.reduce_dir(state["dir"], window_s)}
        if cmd[0] == "memstats":
            return {"ok": True,
                    "memory_peak_bytes": chip.memory_peak_bytes()}
        return {"ok": False, "error": f"unknown command {cmd!r}"}

    while True:
        conn, _ = lsock.accept()
        with conn, conn.makefile("rw") as f:
            for line in f:
                try:
                    out = handle(line.split())
                except Exception as e:  # the answer says what failed
                    out = {"ok": False, "error": repr(e)}
                f.write(json.dumps(out) + "\n")
                f.flush()


def main(argv) -> int:
    from lib import chip

    device = chip.device_or_die(argv[1])
    lsock = socket.socket()
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(4)
    threading.Thread(target=control, args=(lsock, chip.GcPauses()),
                     daemon=True).start()
    print(json.dumps({"event": "control", "port": lsock.getsockname()[1],
                      **device}), flush=True)
    from gatekeeper_tpu.fleet import replica

    return replica.main(argv[2:])


if __name__ == "__main__":
    sys.exit(main(sys.argv))
