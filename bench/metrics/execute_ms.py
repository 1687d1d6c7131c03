"""Runner: mean time of a batch's stack and ``CompiledArtifact.run``,
the engine's ``serve_stage_ms{stage=execute}`` sum over count."""
from bench.readings import histogram_mean


def read(run):
    return histogram_mean(run.engine_metrics, "serve_stage_ms",
                          stage="execute")
