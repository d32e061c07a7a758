"""Harness library: cell loading, traffic, load generation, trace reduction,
operation counts, the plain references and the checks that decide
`correct`."""
