from tnc_tpu.builders.circuit_builder import (  # noqa: F401
    Circuit,
    Permutor,
    QuantumRegister,
    Qubit,
)
from tnc_tpu.builders.connectivity import (  # noqa: F401
    Connectivity,
    ConnectivityLayout,
)
from tnc_tpu.builders.kicked_ising_circuit import (  # noqa: F401
    kicked_ising_circuit,
)
from tnc_tpu.builders.peps import peps  # noqa: F401
from tnc_tpu.builders.random_circuit import (  # noqa: F401
    random_circuit,
    random_circuit_with_observable,
    random_circuit_with_set_observable,
)
from tnc_tpu.builders.sycamore_circuit import sycamore_circuit  # noqa: F401
from tnc_tpu.builders.tensorgeneration import (  # noqa: F401
    random_sparse_tensor_data,
    random_sparse_tensor_data_with_rng,
)
