from repro.evolution.nsga2 import NSGA2Config  # noqa
from repro.evolution import ga  # noqa
from repro.evolution.ga import (GAState, StepRecord, StreamingResult,  # noqa
                                evaluate_population_streaming,
                                init_state, init_state_from_population,
                                make_step, run_generational,
                                select_top_streaming)
from repro.evolution.island import (EpochRecord, IslandState,  # noqa
                                    host_snapshot, init_island_state,
                                    make_epoch, make_recorded_epoch,
                                    make_evolve, make_merge, make_reseed,
                                    make_superstep, place_island_state,
                                    run_islands)
from repro.evolution.archive import Archive, init_archive, merge, pareto_front  # noqa
