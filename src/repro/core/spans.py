"""Names of the round's trace spans, in one place.

Device scopes are ``jax.named_scope`` names: they land in the
``op_name`` metadata of every HLO op traced under them (backward ops as
``transpose(jvp(<name>))``) and cost nothing at run time. Host spans are
``jax.profiler.TraceAnnotation`` names on the scanned engine's chunk
(``FederatedTrainer._run_scan_chunk``); without a profiler each is one
flag check. ``bench/spans.py`` reduces a profiler trace to time per
scope and per host span; the metric each name feeds is given beside it.
"""

# device scopes, inside the jitted round program
SAMPLE = "scaffold.sample"          # data_dev_ms: the cohort draw
BATCHES = "scaffold.batches"        # data_dev_ms: the cohort's batches
GATHER = "scaffold.gather"          # store_dev_ms: cohort rows read
SCATTER = "scaffold.scatter"        # store_dev_ms: cohort rows written
CLIENT = "scaffold.client"          # a client's own ops (dy = y - x)
LOCAL_STEP = "scaffold.local_step"  # local_step_dev_ms, step_idle_ms
APPLY = "update_space.apply"        # merge_dev_ms: deltas into the base
GRAD_PROJECT = "update_space.grad_project"  # project_dev_ms
MODEL_BLOCKS = "model.blocks"       # inside local_step_dev_ms
MODEL_HEAD = "model.head"           # inside local_step_dev_ms
SOLVER_STEP = "solver.step"         # inside local_step_dev_ms
CONTROL = "scaffold.control"        # agg_dev_ms: the client's c_i update
AGGREGATE = "scaffold.aggregate"    # agg_dev_ms: weighted dy/dc means
SERVER = "scaffold.server"          # agg_dev_ms: server step, c, metrics

DEVICE_SCOPES = (SAMPLE, BATCHES, GATHER, SCATTER, CLIENT, LOCAL_STEP,
                 APPLY, GRAD_PROJECT, MODEL_BLOCKS, MODEL_HEAD, SOLVER_STEP,
                 CONTROL, AGGREGATE, SERVER)

# host spans, around one scanned chunk
CHUNK = "scaffold.chunk"        # args rounds, round; self time: bookkeeping
DISPATCH = "scaffold.dispatch"  # dispatch_ms: the jitted chunk's call
FETCH = "scaffold.fetch"        # fetch_ms: the stacked metrics to the host

HOST_SPANS = (CHUNK, DISPATCH, FETCH)
