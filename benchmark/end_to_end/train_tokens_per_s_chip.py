"""Tokens trained in the window over the window's seconds (host clock
round the whole second ``fit``) over the chips used."""


def read(obs):
    return obs["train"]["tokens"] / obs["window_s"] / obs["chips"]
