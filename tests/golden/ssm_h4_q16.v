// Generated by repro.codegen (paper Table I) — spec ssm_2i_1x4_2o

// cell=ssm steps=6 unroll=1 c_slow=1 width=16

module Create_mult #(parameter WIDTH = 16) (
  input  wire                      clk,
  input  wire                      en,
  input  wire                      clr,
  input  wire signed [WIDTH-1:0]   a,     // datapath operand
  input  wire signed [WIDTH-1:0]   w,     // coefficient (ROM port)
  output reg  signed [2*WIDTH-1:0] acc    // wide accumulator
);
  always @(posedge clk) begin
    if (clr)     acc <= {2*WIDTH{1'b0}};
    else if (en) acc <= acc + a * w;
  end
endmodule

module Create_Layer_layer0_drive #(parameter WIDTH = 16, parameter J = 1) (
  input  wire                        clk,
  input  wire                        start,
  input  wire [$clog2(6)-1:0]        k,      // FSM step (ROM page select)
  input  wire signed [2*WIDTH-1:0]  x_bus,  // input bus (2 lanes)
  output wire signed [4*WIDTH-1:0] z_bus,  // MACC results (4 lanes)
  output reg                         done
);
  // coefficient ROM: 1 page(s) x 4x2 words
  reg signed [WIDTH-1:0] rom [0:7];
  reg signed [WIDTH-1:0] rom_b [0:3];  // bias ROM, one word per lane
  initial begin
    rom[0] = 16'h064e;
    rom[1] = 16'hfcfd;
    rom[2] = 16'h00b6;
    rom[3] = 16'hf661;
    rom[4] = 16'h1a27;
    rom[5] = 16'hfef4;
    rom[6] = 16'hf98c;
    rom[7] = 16'h10df;
  end
  initial begin
    rom_b[0] = 16'h0000;
    rom_b[1] = 16'h0000;
    rom_b[2] = 16'h0000;
    rom_b[3] = 16'h0000;
  end
  reg [$clog2(2):0] cyc;  // 2 serial MACC cycles (J = 1 copies)
  genvar gi, ji;
  generate
    for (gi = 0; gi < 4; gi = gi + 1) begin : lane
      // J parallel Create_mult copies stride the input bus; term ji covers
      // element cyc*J + ji (zero-padded past in_width), summed combinationally
      wire signed [2*WIDTH-1:0] acc [0:J-1];
      wire signed [2*WIDTH-1:0] acc_sum [0:J];
      assign acc_sum[0] = {2*WIDTH{1'b0}};
      for (ji = 0; ji < J; ji = ji + 1) begin : copy
        wire [31:0] idx = cyc * J + ji;
        wire        pad = (idx >= 2);
        Create_mult #(.WIDTH(WIDTH)) u_mult (
          .clk(clk), .en(~done & ~pad), .clr(start),
          .a(x_bus[(idx % 2)*WIDTH +: WIDTH]),
          .w(rom[gi*2 + (idx % 2)]),
          .acc(acc[ji])
        );
        assign acc_sum[ji+1] = acc_sum[ji] + acc[ji];
      end
      assign z_bus[gi*WIDTH +: WIDTH] = acc_sum[J][2*WIDTH-1-4 -: WIDTH] + rom_b[gi];  // Q-align
    end
  endgenerate
  always @(posedge clk) begin
    if (start) begin cyc <= 0; done <= 1'b0; end
    else if (!done) begin
      cyc  <= cyc + 1;
      done <= (cyc == 1);
    end
  end
endmodule

module Create_Layer_End_C #(parameter WIDTH = 16, parameter J = 1) (
  input  wire                        clk,
  input  wire                        start,
  input  wire [$clog2(2)-1:0]        k,      // FSM step (ROM page select)
  input  wire signed [4*WIDTH-1:0]  x_bus,  // input bus (4 lanes)
  output wire signed [2*WIDTH-1:0] z_bus,  // MACC results (2 lanes)
  output reg                         done
);
  // coefficient ROM: 1 page(s) x 2x4 words
  reg signed [WIDTH-1:0] rom [0:7];
  initial begin
    rom[0] = 16'h0a5d;
    rom[1] = 16'h0ad7;
    rom[2] = 16'hfcb9;
    rom[3] = 16'hf9d3;
    rom[4] = 16'h030c;
    rom[5] = 16'h0027;
    rom[6] = 16'h0499;
    rom[7] = 16'hf9f9;
  end
  reg [$clog2(4):0] cyc;  // 4 serial MACC cycles (J = 1 copies)
  genvar gi, ji;
  generate
    for (gi = 0; gi < 2; gi = gi + 1) begin : lane
      // J parallel Create_mult copies stride the input bus; term ji covers
      // element cyc*J + ji (zero-padded past in_width), summed combinationally
      wire signed [2*WIDTH-1:0] acc [0:J-1];
      wire signed [2*WIDTH-1:0] acc_sum [0:J];
      assign acc_sum[0] = {2*WIDTH{1'b0}};
      for (ji = 0; ji < J; ji = ji + 1) begin : copy
        wire [31:0] idx = cyc * J + ji;
        wire        pad = (idx >= 4);
        Create_mult #(.WIDTH(WIDTH)) u_mult (
          .clk(clk), .en(~done & ~pad), .clr(start),
          .a(x_bus[(idx % 4)*WIDTH +: WIDTH]),
          .w(rom[gi*4 + (idx % 4)]),
          .acc(acc[ji])
        );
        assign acc_sum[ji+1] = acc_sum[ji] + acc[ji];
      end
      assign z_bus[gi*WIDTH +: WIDTH] = acc_sum[J][2*WIDTH-1-4 -: WIDTH];  // Q-align
    end
  endgenerate
  always @(posedge clk) begin
    if (start) begin cyc <= 0; done <= 1'b0; end
    else if (!done) begin
      cyc  <= cyc + 1;
      done <= (cyc == 3);
    end
  end
endmodule

module Create_Datapath_layer0 #(parameter WIDTH = 16) (
  input  wire clk,
  input  wire start,
  input  wire load,
  input  wire [$clog2(6)-1:0] k,
  input  wire signed [2*WIDTH-1:0] u_bus,
  input  wire signed [4*WIDTH-1:0] h_init,
  output wire signed [4*WIDTH-1:0] h_bus,
  output wire signed [4*WIDTH-1:0] y_bus,
  output wire step_done
);
  wire signed [2*WIDTH-1:0] w_u;  assign w_u = u_bus;
  reg signed [4*WIDTH-1:0] r_h;  // state register
  wire signed [4*WIDTH-1:0] w_h;  assign w_h = r_h;
  // const ROM 'a' [1x4]
  wire signed [4*WIDTH-1:0] w_a = {16'h091f, 16'h094d, 16'h0abf, 16'h0c80};
  // const ROM 'w_in' [2x4]
  // const ROM 'b' [1x4]
  wire signed [4*WIDTH-1:0] w_drive;
  wire d_drive;
  Create_Layer_layer0_drive #(.WIDTH(WIDTH)) u_drive (
    .clk(clk), .start(start), .k(k),
    .x_bus(w_u), .z_bus(w_drive), .done(d_drive)
  );  // 2 -> 4 MACC array + bias ROM
  wire signed [4*WIDTH-1:0] w_ah;  // elementwise mul, 4 VPU lanes
  genvar ei_ah;
  generate
    for (ei_ah = 0; ei_ah < 4; ei_ah = ei_ah + 1) begin : ew_ah
      wire signed [2*WIDTH-1:0] p = $signed(w_a[ei_ah*WIDTH +: WIDTH]) * $signed(w_h[ei_ah*WIDTH +: WIDTH]);
      assign w_ah[ei_ah*WIDTH +: WIDTH] = p[2*WIDTH-1-4 -: WIDTH];
    end
  endgenerate
  wire signed [4*WIDTH-1:0] w_h_next;  // elementwise add, 4 VPU lanes
  genvar ei_h_next;
  generate
    for (ei_h_next = 0; ei_h_next < 4; ei_h_next = ei_h_next + 1) begin : ew_h_next
      assign w_h_next[ei_h_next*WIDTH +: WIDTH] = $signed(w_ah[ei_h_next*WIDTH +: WIDTH]) + $signed(w_drive[ei_h_next*WIDTH +: WIDTH]);
    end
  endgenerate
  assign step_done = d_drive;
  // ONE register write-back per start kick (step_done is a sticky level
  // that only clears on the next start pulse).  AF_DEPTH settle cycles let
  // the registered AF ROM chain propagate the FINAL MACC sum (one clock per
  // chained ROM) before the state registers latch.
  localparam AF_DEPTH = 0;
  reg stepped;
  reg [2:0] af_wait;
  always @(posedge clk) begin
    if (load) begin
      stepped <= 1'b0; af_wait <= 3'd0;
      r_h <= h_init;
    end else if (start) begin
      stepped <= 1'b0; af_wait <= 3'd0;
    end else if (step_done && af_wait < AF_DEPTH) begin
      af_wait <= af_wait + 3'd1;
    end else if (step_done && !stepped) begin
      stepped <= 1'b1;
      r_h <= w_h_next;
    end
  end
  assign h_bus = r_h;
  assign y_bus = w_h_next;
endmodule

module Create_TopModule_ssm_2i_1x4_2o #(parameter WIDTH = 16) (
  input  wire                       clk,
  input  wire                       rst,
  input  wire                       start,
  input  wire signed [2*WIDTH-1:0]   u_bus,
  output wire                       u_ready,  // present u[u_k] on u_bus
  output wire [$clog2(6)-1:0]       u_k,
  output wire signed [2*WIDTH-1:0]  y_bus,
  output reg                        done
);
  // FSM: IDLE -> LOAD -> ITERATE x 6 -> READOUT -> DONE
  localparam S_IDLE = 3'd0, S_LOAD = 3'd1, S_ITER = 3'd2,
             S_READ = 3'd3, S_DONE = 3'd4;
  localparam STEPS = 6, CSLOW = 1, J = 1;
  localparam SETTLE = 2;  // last stage AF chain + write-back

  reg [2:0] fsm_state;
  reg [$clog2(6)-1:0] fsm_k;  // the time-multiplex counter
  // MACC layers treat start as a synchronous clear, so every use is kicked
  // by a ONE-CYCLE pulse; transitions qualify on !kick to let the sticky
  // done levels clear after each kick.
  reg kick;        // per-step start pulse into the first stage datapath
  reg load_kick;   // input-injection start (Create_Layer1)
  reg read_kick;   // readout start (Create_Layer_End)
  reg [2:0] settle;  // AF-ROM chain + write-back cycles before advancing
  wire step_start = kick;
  wire load       = (fsm_state == S_LOAD);
  assign u_ready = kick;
  assign u_k     = fsm_k;
  wire signed [4*WIDTH-1:0] layer0_h;
  wire signed [4*WIDTH-1:0] layer0_h_init = {4*WIDTH{1'b0}};
  wire signed [4*WIDTH-1:0] y_layer0;
  wire done_layer0;
  Create_Datapath_layer0 #(.WIDTH(WIDTH)) u_layer0 (
    .clk(clk), .start(step_start), .load(load), .k(fsm_k),
    .u_bus(u_bus),
    .h_init(layer0_h_init),
    .h_bus(layer0_h),
    .y_bus(y_layer0),
    .step_done(done_layer0)
  );
  reg done_layer0_q;
  always @(posedge clk) done_layer0_q <= done_layer0;
  wire step_done_all = done_layer0 & ~done_layer0_q;
  // recurrent cells: state registers load zero; u_bus streams per step
  wire load_done = 1'b1;
  // Create_Layer_End: readout y = C x[N] on the final carry
  wire signed [4*WIDTH-1:0] x_final = layer0_h;
  wire read_done;
  Create_Layer_End_C #(.WIDTH(WIDTH)) u_readout (
    .clk(clk), .start(read_kick), .k(1'b0),
    .x_bus(x_final), .z_bus(y_bus), .done(read_done)
  );
  always @(posedge clk) begin
    if (rst) begin
      fsm_state <= S_IDLE; fsm_k <= 0; done <= 1'b0;
      kick <= 1'b0; load_kick <= 1'b0; read_kick <= 1'b0; settle <= 3'd0;
    end else begin
      kick <= 1'b0; load_kick <= 1'b0; read_kick <= 1'b0;
      case (fsm_state)
        S_IDLE: if (start) begin fsm_state <= S_LOAD; load_kick <= 1'b1; end
        S_LOAD: if (load_done && !load_kick) begin
          fsm_state <= S_ITER; fsm_k <= 0; kick <= 1'b1;
        end
        S_ITER: begin
          // done EDGE -> SETTLE cycles (AF ROM chain, then register
          // write-back) -> next kick / readout
          if (settle == SETTLE) begin
            settle <= 3'd0;
            if (fsm_k == STEPS - 1) begin fsm_state <= S_READ; read_kick <= 1'b1; end
            else begin fsm_k <= fsm_k + 1; kick <= 1'b1; end  // next use
          end else if (settle != 3'd0) begin
            settle <= settle + 3'd1;
          end else if (step_done_all) begin
            settle <= 3'd1;
          end
        end
        S_READ: if (read_done && !read_kick) fsm_state <= S_DONE;
        S_DONE: begin done <= 1'b1; fsm_state <= S_IDLE; end
      endcase
    end
  end
endmodule
