"""State codes of a probe slot, and the counters a run keeps.

The codes are the ones the deployment's state tables carry, so the tables
of the program and of the reference compare value for value.
"""

EMPTY = 0  # free slot
ROUTING = 1  # sent by the gateway, one hop to its launchpad
ADDRESSING = 2  # at a node, choosing among candidates
BOUNCING = 3  # one hop to the chosen node
QUEUED = 4  # waiting in that node's arbitration
RESERVED = 5  # won arbitration, pulling its payload (or suspended state)
RUNNING = 6  # executing
SUSPENDED = 7  # suspended by Airlock, atoms still held
LOST_WAIT = 8  # hop lost, waiting out the regeneration quiet interval

NUM_TIERS = 3
HIST_BUCKETS = 64

# scalar counters; every one starts at 0
COUNTERS = (
    "arrived", "arrived_f", "arrived_l", "arrived_squat", "dropped_capacity",
    "started", "started_f", "started_l", "completed", "completed_f", "completed_l",
    "fastfail", "lost", "regen_spawned", "regen_exhausted", "timeout",
    "squat_expired", "reserve_expired", "infeasible_winner", "oom_kill_f",
    "oom_kill_l", "suspended_cnt", "resumed_insitu", "reactivated", "migrated",
    "reclaimed", "throttled_rounds", "node_failures", "node_recoveries",
    "evicted", "evicted_killed", "op_dispatch", "op_eval", "op_bounce", "op_arb",
)
# per-tier counters, (NUM_TIERS,) each
TIER_COUNTERS = (
    "started_tier", "completed_tier", "oom_kill_tier", "reclaimed_tier",
    "evicted_killed_tier",
)
