"""Programs JAX built inside the window, compiled or read from the
persistent cache, from its monitoring events (`chipbench.clock`); 0 when
set-up warmed every shape the window uses."""


def read(obs):
    return obs.get("compiles_in_window")
