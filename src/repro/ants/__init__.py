from repro.ants.model import (AntsState, simulate, simulate_batch,  # noqa
                              simulate_batch_state,
                              food_sources, nest_mask, init_state, make_step)
