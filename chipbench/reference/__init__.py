"""Plain references, independent of the code under test."""
