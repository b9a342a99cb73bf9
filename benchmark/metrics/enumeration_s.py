"""Layer: enumeration, host C++.  The harness's span around
``load_config_from_yaml`` and ``basis.build()``."""


def read(run):
    return run.spans.get("enumeration")
