"""Runtime: elastic trainer, local RMS endpoint, serving loop."""
from repro.runtime.local_rms import LocalRMS, scripted_rival
from repro.runtime.serving import Request, Server
from repro.runtime.trainer import ElasticTrainer, TrainerConfig

__all__ = ["LocalRMS", "scripted_rival", "Request", "Server",
           "ElasticTrainer", "TrainerConfig"]
