"""The chip benchmark's own code: generators, reference, trace reduction."""
