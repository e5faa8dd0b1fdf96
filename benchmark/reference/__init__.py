"""The benchmark's plain reference: a frozen copy of the scheduling oracle."""
