"""Iterations a solve needed to first reach the deployment's target
distance: the mean over the window's solves, each read from the recorded
``SolveResult.dist2`` curve at its record points. Moves ``solve_s``."""


def read(obs):
    return obs.counters.get("iters_to_target")
