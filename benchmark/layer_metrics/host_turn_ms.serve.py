"""The host's own part of a loop turn, in ms a step: the window deltas of
the loop thread's phase clocks (``tfos_replica_phase_seconds_total{phase}``,
fed by the ten ``tfos/`` spans) without the two waits for the device
(``decode_fetch``, ``prefill_fetch``) and the ``idle`` sleep, over the
loop's steps in the window.  Beside ``decode_device_ms.serve`` it says
whether queued turns are paced by the device or by the host.  Nothing
where the observer reads no phase clocks."""

WAITS = ("decode_fetch", "prefill_fetch", "idle")


def read(run):
    c = run.get("counters") or {}
    steps = c.get("tfos_replica_steps_total")
    phases = {k.split(".", 1)[1]: v for k, v in c.items()
              if k.startswith("phase_seconds.")}
    if run["kind"] != "serve-closed" or not steps or not phases:
        return None
    return 1e3 * sum(v for k, v in phases.items() if k not in WAITS) / steps
