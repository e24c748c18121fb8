from repro.kernels import ref
