from repro.serve.vfl import (ScoreRequest, ServeStats, SimReport,
                             VFLScoringEngine, score_partition,
                             simulate_trace)

__all__ = [
    "ScoreRequest", "ServeStats", "SimReport", "VFLScoringEngine",
    "score_partition", "simulate_trace",
]
