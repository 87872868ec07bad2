"""How uneven the experts' load was: the busiest expert's assignments x the
number of experts / the assignments made, over the window's decode and
prefill dispatches and all expert layers (the program's counters
``tfos_replica_expert_peak_assignments_total`` and
``tfos_replica_expert_assignments_total``, window deltas).  1.0 is an even
load; a grouped matmul waits for its largest group."""


def read(run):
    c = run.get("counters") or {}
    experts = run["cell"]["config_data"].get("num_experts")
    made = c.get("tfos_replica_expert_assignments_total")
    if not experts or not made:
        return None
    return c["tfos_replica_expert_peak_assignments_total"] * experts / made
