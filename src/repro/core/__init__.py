# The paper's primary contribution: routing DNN inference jobs over a
# distributed computing network via the layered-graph model (§III) and the
# greedy / simulated-annealing algorithms (§IV), implemented as composable
# JAX modules (jit/vmap/lax throughout; min-plus closures back onto the
# Pallas tropical-matmul kernel in repro.kernels).
from .network import (ComputeNetwork, INF, make_network, small_topology,
                      us_backbone)
from .state import (QueueState, Topology, advance, backlog_seconds,
                    total_backlog)
from .jobs import InferenceJob, JobBatch, batch_jobs, synthetic_job
from . import arrivals
from .routing import (Route, route_single, route_batch,
                      cost_given_assignment, commit_assignment)
from .shortest_path import (Closures, build_closures, build_closures_batch,
                            closure_build_count)
from .plan import Plan
from .solvers import Solver, solve, register as register_solver, \
    available as available_solvers
from .greedy import GreedySolution, greedy_route
from .annealing import SAResult, anneal, evaluate_solution
from .schedule import SimResult, replay_solution, simulate
from .eventsim import EventEngine
from .completions import (CommittedWork, LedgerJob, drain_exact,
                          exact_backlog_trace, replay_piecewise,
                          run_to_completion)
from . import (bounds, completions, eventsim, exact, layered_graph,
               shortest_path, solvers, telemetry)

__all__ = [
    "ComputeNetwork", "INF", "make_network", "small_topology", "us_backbone",
    "Topology", "QueueState", "advance", "backlog_seconds", "total_backlog",
    "arrivals",
    "InferenceJob", "JobBatch", "batch_jobs", "synthetic_job",
    "Route", "route_single", "route_batch", "cost_given_assignment",
    "commit_assignment",
    "Closures", "build_closures", "build_closures_batch",
    "closure_build_count",
    "Plan", "Solver", "solve", "register_solver", "available_solvers",
    "GreedySolution", "greedy_route",  # deprecated alias + legacy name
    "SAResult", "anneal", "evaluate_solution",
    "SimResult", "replay_solution", "simulate", "EventEngine",
    "CommittedWork", "LedgerJob", "drain_exact", "exact_backlog_trace",
    "replay_piecewise", "run_to_completion",
    "bounds", "completions", "eventsim", "exact", "layered_graph",
    "shortest_path", "solvers", "telemetry",
]
