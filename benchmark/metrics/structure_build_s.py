"""Layer: structure build.  The engine's own timer scope
``build_structure`` (``LocalEngine``) or ``build_plan``
(``DistributedEngine``)."""


def read(run):
    return run.timers.get("structure_build_s") or None
