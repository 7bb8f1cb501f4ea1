"""A number the runner took on the host's clock or from the program's
registry and counters: ``obs["host"][params["key"]]``."""


def read(obs: dict, params: dict):
    return obs["host"].get(params["key"])
