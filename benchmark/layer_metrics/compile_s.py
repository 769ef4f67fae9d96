"""Seconds the program's ``compileobs`` recorded compiling or loading
programs during set-up."""


def read(obs):
    return obs["compile"]["setup_compile_s"]
