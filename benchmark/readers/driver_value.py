"""A client-side number the cell's driver computed anyway, recorded as a
per-layer metric because it does not repeat well enough to be judged.

params: ``name`` (the driver's key for it)."""


def read(obs, params):
    return obs["end_to_end"].get(params["name"])
