"""POAS chip benchmark: harness, yardstick and per-metric readers."""
