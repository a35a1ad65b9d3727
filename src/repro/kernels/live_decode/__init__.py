"""Decode attention over the live K/V lines of a stacked slot cache,
registered as the `live_decode` kernel family."""
