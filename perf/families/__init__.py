"""Circuit families: one module each, ``gates(spec, rng)`` -> gate list."""
