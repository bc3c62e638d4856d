"""Sum of the ``phase:bsign_*`` span durations of a wave (every cohort's
nonce-commit, aggregate-partial and combine-verify phase, each ending in
``block_until_ready``), mean over the nodes and the measured waves. Host
clock, from the program's flight recorder."""


def read(run):
    spans = [s for s in run.spans
             if s["name"].startswith("phase:bsign_")
             and s.get("t1_ns") is not None
             and s["t0_ns"] >= run.window_start_ns]
    waves = len(run.measured_waves)
    if not spans or not waves:
        return None
    nodes = {s.get("node") for s in spans}
    total_ms = sum(s["t1_ns"] - s["t0_ns"] for s in spans) / 1e6
    return total_ms / len(nodes) / waves
