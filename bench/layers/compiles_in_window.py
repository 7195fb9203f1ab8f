"""Model harness: compile requests (``jax.monitoring``'s backend-compile
events, persistent-cache hits included) between the end of warm-up and the
end of the window.  Expected 0."""


def read(trace: dict, run: dict):
    return run.get("compiles_in_window")
