"""The property tests' one import point for hypothesis."""
from hypothesis import given, settings, strategies  # noqa: F401
