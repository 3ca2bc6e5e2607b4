"""Exchange: device-busy seconds of the traced query in the all-to-all
program (XLA module ``jit_exchange_all_to_all``, the one program that
spans the mesh; ``spark_rapids_tpu/programs.py``), averaged over the
chips by ``module_busy.py``: the local sort by destination, the
collective itself and the landing reshape."""
import module_busy

MODULE = "jit_exchange_all_to_all"


def read(reading):
    return module_busy.family_busy_s(reading, MODULE)
