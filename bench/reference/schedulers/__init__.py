"""The deterministic scheduler twins of the reference."""
