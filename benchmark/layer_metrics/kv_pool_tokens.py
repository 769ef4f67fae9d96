"""Tokens the KV pool can hold: ``pool.num_usable x block_size``."""


def read(obs):
    return obs.get("pool_tokens")
