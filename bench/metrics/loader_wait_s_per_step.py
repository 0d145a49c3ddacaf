"""Mean seconds per window step that the training loop waited in
``next(loader)`` for its batch (the benchmark's own span)."""


def reduce(view):
    n, s = view.get("loader_wait", (0, 0.0))
    return s / n if n else None
