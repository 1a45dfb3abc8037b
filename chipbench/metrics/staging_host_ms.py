"""Host time per training step staging the step's grains
(``trainer.source.load_stacked``, ``data/grains.py``), from span
``staging``."""


def read(run):
    d = ((run.get("trace") or {}).get("spans") or {}).get("staging")
    return 1e3 * sum(d) / len(d) if d else None
