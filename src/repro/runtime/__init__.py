"""Distributed runtime: sharding rules, train/serve loops, FT, elasticity."""
# imported first so that its compile listener sees every program the
# runtime compiles
from repro.runtime import telemetry  # noqa: F401
from repro.runtime.sharding import (  # noqa: F401
    axis_rules, batch_shardings, cache_shardings, param_shardings,
    shardings_for, train_state_shardings,
)
from repro.runtime.train_loop import (  # noqa: F401
    TrainState, make_grain_step, make_train_step, train_state_init,
)
from repro.runtime.serve_loop import HeMTBatcher, make_serve_step  # noqa: F401
from repro.runtime.serving import (  # noqa: F401
    RequestModel, ServingReport, ServingScenario, run_round,
)
