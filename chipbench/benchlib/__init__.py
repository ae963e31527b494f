"""The chip benchmark's library: cell lookup, traffic, the serving
window, the work a step needs, trace reduction and the check of the
served tokens against the plain reference."""
