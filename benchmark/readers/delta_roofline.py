"""The audit delta sweep's share of the HBM roofline, in percent: the
bytes the sweeps of the traced window had to move (lib/peaks.py
delta_sweep_bytes, from the configuration's sizes, whatever implements
the sweep) over the chip's HBM bandwidth, over the device time of the
programs whose name holds `kernel` in the trace."""

from lib import peaks


def read(raw: dict, args: dict):
    tr = raw.get("trace")
    if not tr:
        return None
    secs = sum(s for name, s in tr["op_seconds"].items()
               if args["kernel"] in name.split("/")[0])
    sweeps = tr.get("sweeps")
    if not secs or not sweeps:
        return None
    need = peaks.delta_sweep_bytes(raw["sizes"], raw["rows_per_step"])
    least_s = sweeps * need / peaks.peaks(
        raw["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / secs
