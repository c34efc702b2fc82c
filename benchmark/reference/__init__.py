"""Plain references: independent of ``gelly_tpu``, imported by nothing of it."""
