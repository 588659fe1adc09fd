"""The yardstick: everything the benchmark measures with lives here, so a PR
that claims a gain cannot move it."""
