"""Drivers: serve_gp (distributed-GP serving) and fleet (the multi-tenant
fleet server).  Modules are runnable via python -m."""
