from repro.runtime.trainer import (  # noqa: F401
    ElasticRestart,
    PopulationTrainer,
    PopulationTrainerConfig,
    Trainer,
    TrainerConfig,
)
from repro.runtime.straggler import StragglerWatchdog  # noqa: F401
from repro.runtime.scheduler import SlotScheduler  # noqa: F401
from repro.runtime.server import Server, Request  # noqa: F401
from repro.runtime.stream_server import StreamRequest, StreamServer  # noqa: F401
from repro.runtime.autotuner import WarmPoolAutotuner  # noqa: F401
