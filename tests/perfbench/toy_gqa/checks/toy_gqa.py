"""The toy family is never run, so nothing is ever checked."""
