"""Compiles ``compileobs`` counted inside the window. Must be 0."""


def read(obs):
    return obs["compile"]["compiles_in_window"]
