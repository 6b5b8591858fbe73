"""The benchmark's yardstick: data, traffic, reference, work and trace
reduction. numpy + stdlib only; nothing here imports `pilosa_tpu` or jax
(`xplane.py`, run as a process of its own, is the one reader of jax's
profile format)."""
