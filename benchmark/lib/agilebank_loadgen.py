#!/usr/bin/env python3
"""The load generator of the agilebank deployment's webhook role:
lib/loadgen.py's process, commands and loops (closed and open), with
the bodies of lib/agilebank_reviews.py in place of its Pods.

    python3 benchmark/lib/agilebank_loadgen.py <spec.json>

The spec is loadgen's, with `config` (the deployment's sizes and
shares) and the traffic's shares beside it.  Like loadgen.py it imports
nothing of the program.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import agilebank_reviews, loadgen  # noqa: E402

if __name__ == "__main__":
    # the Generator builds its bodies through its module's name
    loadgen.build_bodies = agilebank_reviews.build_bodies
    sys.exit(loadgen.main(sys.argv))
