#!/usr/bin/env python3
"""The door as a process of its own, as a deployment runs it: one
EventFrontDoor speaking GKW1 to one replica.

    python3 benchmark/lib/door.py <wire port> <probe port> <replica id>

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
    door = EventFrontDoor(
        [{"host": "127.0.0.1", "port": int(argv[1]),
          "probe_port": int(argv[2]), "replica_id": argv[3]}],
        probe_interval_s=3600.0).start()
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
