"""An architecture family for the benchmark's tests: the dense decoder's
reference, weight scales and counts under another name, with its own
``program_keys``. ``test_chipbench_family.py`` copies it into a copy of the
benchmark as ``reference/dense_twin.py``, beside a configuration that names
it, to show that a family joins the benchmark as files only."""
import common

_dense = common.family("dense")
globals().update({k: v for k, v in vars(_dense).items()
                  if not k.startswith("_")})


def program_keys(cfg) -> dict:
    """Dense's keys; the program's architecture is this family's where it
    is dense's."""
    keys = _dense.program_keys(cfg)
    if keys["reference"] == "dense":
        keys["reference"] = "dense_twin"
    return keys
