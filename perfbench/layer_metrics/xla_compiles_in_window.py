"""xla_compiles_in_window - layer: compile. Source: program_counter.
JAX's own backend-compile events in the serving process between the
window's first request and its last fetch, less those the persistent
cache answered: programs XLA really compiled while requests waited. The
launcher counts them in traced and untraced runs alike. Moves
queries_per_s."""


def read(run: dict):
    a, b = run["t_start"], run["t_last"]
    built = sum(a <= t <= b for t, _ in run["stats"]["builds"])
    hits = sum(a <= t <= b for t in run["stats"]["cache_hits"])
    return float(built - hits)
