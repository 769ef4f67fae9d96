"""Passes through a looped stack per program step of the window (a prefill,
an inner decode step): ``loop_steps`` while every token takes every pass,
4.0 for Ouro-2.6B; a change that skips a pass shows here
(``stats()["looped"]["passes_per_step"]`` over the window, from the loop's
records)."""
from benchmark.layer_metrics._looped import passes_per_step as read  # noqa: F401
