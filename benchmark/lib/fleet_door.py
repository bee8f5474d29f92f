#!/usr/bin/env python3
"""The door of a fleet as a process of its own: one EventFrontDoor
speaking GKW1 to several replicas, choosing by the roster's policy and
probing ejected backends at the roster's own interval (lib/door.py is
the one-backend door, which never has anything to probe).

    python3 benchmark/lib/fleet_door.py <policy> <wire port>:<probe port>:<replica id> ...

Prints {"event": "door", "port": N} and serves until stdin closes.
"""

import json
import logging
import sys


def main(argv) -> int:
    from gatekeeper_tpu import logging as gklog
    from gatekeeper_tpu.fleet import EventFrontDoor

    gklog.setup("WARNING", stream=sys.stderr)
    logging.getLogger("gatekeeper.obs").setLevel(logging.ERROR)
    backends = []
    for arg in argv[2:]:
        wire, probe, rid = arg.split(":")
        backends.append({"host": "127.0.0.1", "port": int(wire),
                         "probe_port": int(probe), "replica_id": rid})
    door = EventFrontDoor(backends, policy=argv[1]).start()
    print(json.dumps({"event": "door", "port": door.port}), flush=True)
    try:
        for _line in sys.stdin:
            pass
    except KeyboardInterrupt:
        pass
    door.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
