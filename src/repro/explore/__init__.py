from repro.explore.sampling import (Sampling, GridSampling, UniformSampling,  # noqa
                                    LHSSampling, SobolSampling, SeedSampling,
                                    CrossSampling)
from repro.explore.statistics import StatisticTask, median, mean, std, q  # noqa
from repro.explore.replication import (Replicate, replicated,  # noqa
                                      replicated_batch,
                                      replicated_batch_state)
from repro.explore.surrogate import (SurrogateConfig, SurrogateExplorer,  # noqa
                                     SurrogateResult, run_surrogate)
from repro.explore.moacq import (MOSurrogateConfig, MOSurrogateExplorer,  # noqa
                                 MOSurrogateResult, run_surrogate_mo)
